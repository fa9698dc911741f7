"""The grouped expert kernel (ops/pallas_experts.py) in Pallas interpret
mode against the `lax.switch` loop it replaces on a TPU
(models/deepseek.grouped_experts), at widths a CPU test holds; the
route's choice; and the set-up cost it was built around: a program of
two expert layers traces the kernel once."""
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import deepseek as ds
from skypilot_tpu.models import nemotron_h as nh
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.ops import pallas_experts as pe

TOKENS, TOP, HELD, DIM = 64, 4, 8, 128

# Which held expert (0..HELD-1, or HELD: an expert another chip holds)
# each of a call's TOKENS x TOP assignments goes to, and which tokens
# are live; the rows are sorted as MoEByShare sorts them.
PATTERNS = {
    'uneven': lambda r: (r.choice(HELD + 1, (TOKENS, TOP), p=np.r_[
        0.3, 0.2, 0.15, 0.1, 0.08, 0.06, 0.04, 0.02, 0.05]), None),
    'zero_count_experts': lambda r: (r.choice([1, 4, 6, HELD],
                                              (TOKENS, TOP)), None),
    'one_expert_takes_every_row': lambda r: (np.full((TOKENS, TOP), 5),
                                             None),
    # 100 rows to expert 0, then 60 to expert 1: expert 1 starts inside
    # the first block of 128 rows and ends inside the second.
    'block_straddles_two_experts': lambda r: (np.r_[
        np.zeros(100), np.ones(60), np.full(96, 3)].reshape(
            TOKENS, TOP).astype(int), None),
    'rows_past_the_sum': lambda r: (np.where(
        r.random((TOKENS, TOP)) < 0.7, HELD,
        r.integers(0, HELD, (TOKENS, TOP))), None),
    'live_mask_with_dead_lanes': lambda r: (
        r.integers(0, HELD, (TOKENS, TOP)), r.random(TOKENS) < 0.4),
}


def _sorted_rows(chosen, live, x):
    """x_sorted and counts as MoEByShare makes them: the assignments
    of live tokens to held experts first, by expert; the rest after."""
    here = chosen < HELD
    if live is not None:
        here &= live[:, None]
    local = jnp.where(here, chosen, HELD).reshape(-1)
    order = jnp.argsort(local, stable=True)
    counts = jnp.sum(local[:, None] == jnp.arange(HELD)[None, :],
                     axis=0).astype(jnp.int32)
    return x[order // TOP], counts


def _experts(key, act, width, dtype):
    n_mat = pe.ACTS[act]
    keys = jax.random.split(key, HELD * n_mat)
    return [tuple(
        (0.05 * jax.random.normal(
            keys[e * n_mat + j],
            (width, DIM) if j == n_mat - 1 else (DIM, width))).astype(dtype)
        for j in range(n_mat)) for e in range(HELD)]


@pytest.mark.parametrize('pattern', sorted(PATTERNS))
@pytest.mark.parametrize('act', ['relu2', 'swiglu'])
@pytest.mark.parametrize('tiled', [False, True],
                         ids=['whole_expert', 'width_tiles'])
def test_kernel_equals_the_switch_loop(pattern, act, tiled, monkeypatch):
    """Every row of the kernel's output is the loop's: to the bit for a
    whole expert (bf16 products accumulated in float32 on both sides),
    within one bf16 step where the width is cut in tiles (their float32
    partial sums are added in another order, so a value on a rounding
    edge may round the other way); and every row past sum(counts) is
    zero: uneven and zero counts, one
    expert with every row, a block two experts share, rows that belong
    to no expert here, dead lanes."""
    rng = np.random.default_rng(sorted(PATTERNS).index(pattern))
    chosen, live = PATTERNS[pattern](rng)
    width = 256
    if tiled:
        # Two slots of one 128-wide tile of each matrix and no more:
        # the width streams in three tiles (and at its own width no
        # other test's compiled call is reused).
        width = 384
        monkeypatch.setattr(pe, '_WEIGHT_BUDGET',
                            2 * pe.ACTS[act] * DIM * 128 * 2)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (TOKENS, DIM), jnp.bfloat16)
    x_sorted, counts = _sorted_rows(
        jnp.asarray(chosen), None if live is None else jnp.asarray(live), x)
    experts = _experts(jax.random.fold_in(key, 1), act, width,
                       jnp.bfloat16)
    got = pe.grouped_experts_pallas(x_sorted, counts, experts, act=act,
                                    interpret=True)
    want = ds.grouped_experts(x_sorted, counts, experts, TOKENS,
                              ds.EXPERT_FNS[act][0])
    total = int(counts.sum())
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0,
                               rtol=2.0 ** -7 if tiled else 0)
    assert not np.any(np.asarray(got[total:], np.float32))
    assert np.any(np.asarray(got[:total], np.float32)) == (total > 0)


class _TwoExpertLayers(nn.Module):
    """Two of Nemotron-H's expert layers, one after the other."""
    config: nh.NemotronHConfig

    @nn.compact
    def __call__(self, x, live):
        cfg = self.config
        for i in range(2):
            x = x + ds.MoEByShare(
                cfg, expert_act='relu2', latent_dim=cfg.moe_latent_dim,
                shared_dim=cfg.moe_shared_dim, name=f'layer_{i}')(
                    x, live, count=True)
        return x


def test_two_expert_layers_trace_the_kernel_once(monkeypatch):
    """The call is jitted on its own: a program of two expert layers
    traces the kernel's wrapper ONCE (a kernel traced at every call
    site puts its lowering into every warm start), and the program's
    output and device counters through the kernel equal the loop's,
    dead lanes and all."""
    cfg = nh.NemotronHConfig.tiny(moe_latent_dim=128, moe_dim=256)
    layers = _TwoExpertLayers(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 1, cfg.embed_dim),
                          jnp.bfloat16)
    live = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0], bool)[:, None]
    variables = layers.init(jax.random.PRNGKey(0), x, live)

    def run():
        return jax.jit(lambda v, x, live: layers.apply(
            v, x, live, mutable=['cache']))(variables, x, live)

    loop_out, loop_counters = run()
    traced = []
    plan = pe._plan

    def counting_plan(*args):
        traced.append(args)
        return plan(*args)

    monkeypatch.setattr(pe, '_plan', counting_plan)
    monkeypatch.setattr(pe, 'resolve_route', lambda *a: 'grouped_pallas')
    jax.clear_caches()
    kernel_out, kernel_counters = run()
    assert len(traced) == 1
    np.testing.assert_allclose(np.asarray(kernel_out, np.float32),
                               np.asarray(loop_out, np.float32), atol=1e-6)
    assert jax.tree.structure(kernel_counters) == jax.tree.structure(
        loop_counters)
    for got, want in zip(jax.tree.leaves(kernel_counters),
                         jax.tree.leaves(loop_counters)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize('shape, why', [
    ((1024, 2688, 2, jnp.bfloat16), None),     # Nemotron 3 Super
    ((7168, 2048, 3, jnp.bfloat16), None),     # DeepSeek-V3.2
    ((32, 64, 2, jnp.bfloat16), 'not a multiple of 128 lanes'),
    ((1024, 2600, 2, jnp.bfloat16), 'not a multiple of 128 lanes'),
    ((1024, 2688, 2, jnp.int8), 'is not bf16/f16/f32'),
    ((65536, 2048, 3, jnp.bfloat16), 'MiB budget'),
], ids=['nemotron3_super', 'deepseek_v32', 'nemotron_h_tiny',
        'odd_width', 'int8', 'over_budget'])
def test_route_is_the_kernel_on_a_tpu_for_shapes_it_takes(shape, why,
                                                          monkeypatch):
    """Off a TPU every shape takes the loop; on one, the kernel unless
    `kernel_refusal` names why not, and the kernel called on a refused
    shape says so by name."""
    assert pe.resolve_route(*shape) == 'switch_loop'
    assert pe.kernel_refusal(*shape) is None if why is None else (
        why in pe.kernel_refusal(*shape))
    monkeypatch.setattr(pe, 'available', lambda: True)
    assert pe.resolve_route(*shape) == (
        'grouped_pallas' if why is None else 'switch_loop')
    if why is not None:
        dim, width, n_mat, dtype = shape
        act = {2: 'relu2', 3: 'swiglu'}[n_mat]
        experts = [tuple(jax.ShapeDtypeStruct(
            (width, dim) if j == n_mat - 1 else (dim, width), dtype)
            for j in range(n_mat))]
        with pytest.raises(ValueError, match='grouped_pallas'):
            pe.grouped_experts_pallas(
                jax.ShapeDtypeStruct((16, dim), dtype),
                jax.ShapeDtypeStruct((1,), jnp.int32), experts, act=act)


def test_route_under_a_mesh_of_several_devices_is_the_loop(monkeypatch):
    """The call is opaque to GSPMD: under a mesh of more than one device
    the loop keeps the pass."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    if len(jax.devices()) < 2:
        pytest.skip('needs >= 2 host devices')
    monkeypatch.setattr(pe, 'available', lambda: True)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    with mesh:
        assert pe.resolve_route(1024, 2688, 2, jnp.bfloat16) == (
            'switch_loop')
    assert pe.resolve_route(1024, 2688, 2, jnp.bfloat16) == (
        'grouped_pallas')


@pytest.mark.parametrize('model, on_a_tpu', [
    (nh.NemotronH(nh.NemotronHConfig.super_l11_ep4()), 'grouped_pallas'),
    (nh.NemotronH(nh.NemotronHConfig.tiny()), 'switch_loop'),
    (ds.Deepseek(ds.DeepseekConfig.v32_l5_ep16()), 'grouped_pallas'),
    (types.SimpleNamespace(), 'none'),
], ids=['nemotron3_super', 'nemotron_h_tiny', 'deepseek_v32', 'no_experts'])
def test_stats_names_the_expert_route(model, on_a_tpu, monkeypatch):
    """/stats `expert_impl` is the route the model's grouped pass
    resolves to: the loop off a TPU and for a refused shape, the
    kernel on a TPU for a published width, 'none' without experts."""
    engine = types.SimpleNamespace(model=model, mesh=None)
    route = ContinuousBatchingEngine.expert_impl
    assert route(engine) == ('none' if on_a_tpu == 'none'
                             else 'switch_loop')
    monkeypatch.setattr(pe, 'available', lambda: True)
    assert route(engine) == on_a_tpu
