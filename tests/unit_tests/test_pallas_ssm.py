"""The state update kernel (ops/pallas_ssm.py) in Pallas interpret mode
against the live rows' loop it replaces on a TPU (ops/ssm.ssm_update,
route 'live_rows_loop') and against the recurrence step by step
(`ssm_reference`), at the tiny preset's widths and at Nemotron 3 Super's
H, P, N with fewer slots; the route's choice; and the set-up cost it
was built around: a program of two Mamba layers traces the kernel
once."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import nemotron_h as nh
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.ops import pallas_ssm as ps
from skypilot_tpu.ops import ssm

# (heads, head size, state, groups, slots): the tiny preset's mixer, and
# the published widths over four slots.
WIDTHS = {'nemotron_h_tiny': (8, 16, 16, 2, 6),
          'nemotron3_super': (128, 64, 128, 8, 4)}
TAPS = 4
# Which lanes are live, by the slots there are.
LIVE = {'no_lane': lambda slots: [],
        'every_lane': lambda slots: list(range(slots)),
        'scattered': lambda slots: [slots - 1, 1]}


def _operands(heads, hd, n, groups, slots, d_nonzero, seed=0):
    width = heads * hd + 2 * groups * n
    state_row, tail_row = ssm.slot_shapes(heads, hd, n, TAPS, width)
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf16 = jnp.bfloat16
    return dict(
        state=jax.random.normal(k[0], (slots,) + state_row),
        tail=jax.random.normal(k[1], (slots,) + tail_row, bf16),
        xbc=jax.random.normal(k[2], (slots, width), bf16),
        dt=jax.nn.softplus(jax.random.normal(k[3], (slots, heads)) - 1.0),
        a=-jnp.exp(jax.random.normal(k[4], (heads,))),
        d=(jax.random.normal(k[5], (heads,)) if d_nonzero
           else jnp.zeros((heads,))),
        weight=jax.random.uniform(k[6], (TAPS, width), minval=-0.5,
                                  maxval=0.5),
        bias=jax.random.uniform(k[7], (width,), minval=-0.5, maxval=0.5))


def _update(ops, live, groups, route, monkeypatch=None):
    """`ssm_update` by `route`; given `monkeypatch`, the kernel runs in
    interpret mode."""
    if monkeypatch is not None:
        call = ps.state_update_pallas
        monkeypatch.setattr(ps, 'state_update_pallas',
                            lambda *a, **kw: call(*a, interpret=True, **kw))
    return ssm.ssm_update(ops['state'], ops['tail'], ops['xbc'], ops['dt'],
                          ops['a'], ops['d'], ops['weight'], ops['bias'],
                          live, groups=groups, route=route)


@pytest.mark.parametrize('d_nonzero', [False, True], ids=['d_zero',
                                                          'd_nonzero'])
@pytest.mark.parametrize('lanes', sorted(LIVE))
@pytest.mark.parametrize('widths', sorted(WIDTHS))
def test_kernel_equals_the_loop_and_the_recurrence(widths, lanes,
                                                   d_nonzero, monkeypatch):
    """A live lane's y and state are the loop's and the recurrence's to
    1e-5 (float32 both sides; y's sum over the state runs in another
    order) and its tail the loop's to the bit; a dead lane's state and
    tail are its input's to the bit and its y zeros."""
    heads, hd, n, groups, slots = WIDTHS[widths]
    ops = _operands(heads, hd, n, groups, slots, d_nonzero)
    live = np.zeros(slots, bool)
    live[LIVE[lanes](slots)] = True
    got = _update(ops, jnp.asarray(live), groups, 'state_pallas',
                  monkeypatch)
    want = _update(ops, jnp.asarray(live), groups, 'live_rows_loop')
    y, state, tail = (np.asarray(v, np.float32) for v in got)
    assert y.shape == (slots, heads, hd)
    assert state.shape == ops['state'].shape and tail.shape == \
        ops['tail'].shape
    np.testing.assert_allclose(y, np.asarray(want[0]), atol=1e-5, rtol=0)
    np.testing.assert_allclose(state, np.asarray(want[1]), atol=1e-5,
                               rtol=0)
    np.testing.assert_array_equal(tail, np.asarray(want[2], np.float32))
    # The recurrence step by step from the same activation.
    taps = ssm.tail_from_slot(ops['tail'], TAPS)
    act = ssm.conv_step(jnp.moveaxis(taps, 1, 0), ops['xbc'],
                        ops['weight'], ops['bias'], jnp.bfloat16)
    inner, bc = heads * hd, groups * n
    y_ref, h_ref = ssm.ssm_reference(
        act[:, None, :inner].reshape(slots, 1, heads, hd),
        ops['dt'][:, None], ops['a'],
        act[:, None, inner:inner + bc].reshape(slots, 1, groups, n),
        act[:, None, inner + bc:].reshape(slots, 1, groups, n), ops['d'],
        ssm.state_from_slot(ops['state']), jnp.ones(slots, jnp.int32))
    shifted = ssm.tail_to_slot(jnp.concatenate(
        [taps[:, 1:], ops['xbc'][:, None]], axis=1), heads)
    for lane in range(slots):
        if live[lane]:
            np.testing.assert_allclose(y[lane], np.asarray(y_ref[lane, 0]),
                                       atol=1e-5, rtol=0)
            np.testing.assert_allclose(
                state[lane], np.asarray(ssm.state_to_slot(h_ref[lane])),
                atol=1e-5, rtol=0)
            np.testing.assert_array_equal(
                tail[lane], np.asarray(shifted[lane], np.float32))
        else:
            np.testing.assert_array_equal(state[lane],
                                          np.asarray(ops['state'][lane]))
            np.testing.assert_array_equal(
                tail[lane], np.asarray(ops['tail'][lane], np.float32))
            assert not y[lane].any()


def test_two_mamba_layers_trace_the_kernel_once(monkeypatch):
    """The call is jitted on its own: two layers' updates in one program
    trace the kernel ONCE, and the second layer's output through the
    kernel equals the loop's."""
    heads, hd, n, groups, slots = WIDTHS['nemotron_h_tiny']
    ops = _operands(heads, hd, n, groups, slots, True, seed=3)
    live = jnp.asarray([True, False, True, True, False, False])

    def two_layers(route):
        def run(ops):
            y, state, tail = _update(ops, live, groups, route)
            ops = dict(ops, state=state, tail=tail)
            return _update(ops, live, groups, route)
        return jax.jit(run)(ops)

    want = two_layers('live_rows_loop')
    traced = []
    kernel = ps._kernel

    def counting_kernel(*args):
        traced.append(1)
        return kernel(*args)

    monkeypatch.setattr(ps, '_kernel', counting_kernel)
    call = ps.state_update_pallas
    monkeypatch.setattr(ps, 'state_update_pallas',
                        lambda *a, **kw: call(*a, interpret=True, **kw))
    jax.clear_caches()
    got = two_layers('state_pallas')
    assert len(traced) == 1
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=1e-5)


@pytest.mark.parametrize('shape, why', [
    ((128, 64, 128, 8, 10240, 4, jnp.bfloat16, 128), None),  # Nemotron 3
    ((8, 16, 16, 2, 192, 4, jnp.bfloat16, 4), 'rows of 128 lanes'),  # tiny
    ((128, 64, 64, 8, 9216, 4, jnp.bfloat16, 128), 'not one lane row'),
    ((128, 64, 128, 8, 10240, 4, jnp.int8, 128), 'is not bf16/f16/f32'),
    ((128, 48, 128, 8, 8192, 4, jnp.bfloat16, 128), 'do not fill rows'),
    ((128, 64, 128, 8, 10240, 4, jnp.bfloat16, 1024), 'MiB it may hold'),
], ids=['nemotron3_super', 'nemotron_h_tiny', 'state_under_the_heads',
        'int8', 'head_size_48', 'y_over_its_vmem'])
def test_route_is_the_kernel_on_a_tpu_for_shapes_it_takes(shape, why,
                                                          monkeypatch):
    """Off a TPU every shape takes the loop; on one, the kernel unless
    `kernel_refusal` names why not, and the kernel called on a refused
    shape says so by name."""
    assert ps.resolve_route(*shape) == 'live_rows_loop'
    assert ps.kernel_refusal(*shape) is None if why is None else (
        why in ps.kernel_refusal(*shape))
    monkeypatch.setattr(ps, 'available', lambda: True)
    assert ps.resolve_route(*shape) == (
        'state_pallas' if why is None else 'live_rows_loop')
    if why is not None:
        heads, hd, n, groups, width, taps, dtype, slots = shape
        f32 = jnp.float32
        with pytest.raises(ValueError, match='state_pallas'):
            ps.state_update_pallas(
                jax.ShapeDtypeStruct((slots, hd, n, heads), f32),
                jax.ShapeDtypeStruct((slots, 1, heads), dtype),
                jax.ShapeDtypeStruct((slots, 1, heads), dtype),
                jax.ShapeDtypeStruct((slots, heads), f32),
                jax.ShapeDtypeStruct((heads,), f32),
                jax.ShapeDtypeStruct((heads,), f32),
                jax.ShapeDtypeStruct((taps, width // heads, heads), f32),
                jax.ShapeDtypeStruct((width // heads, heads), f32),
                jax.ShapeDtypeStruct((slots,), jnp.int32), 1, groups=groups,
                act_dtype=dtype)


def test_route_is_the_loop_inside_a_mesh_of_several_devices(monkeypatch):
    """The call is opaque to GSPMD: inside a mesh of more than one
    device the published widths take the loop, on one device the
    kernel."""
    monkeypatch.setattr(ps, 'available', lambda: True)
    shape = (128, 64, 128, 8, 10240, 4, jnp.bfloat16, 128)
    for devices, route in ((1, 'state_pallas'), (2, 'live_rows_loop')):
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:devices]),
                                 ('tensor',))
        with mesh:
            assert ps.resolve_route(*shape) == route


@pytest.mark.parametrize('model, on_a_tpu', [
    (nh.NemotronH(nh.NemotronHConfig.super_l11_ep4()), 'state_pallas'),
    (nh.NemotronH(nh.NemotronHConfig.tiny()), 'live_rows_loop'),
    (types.SimpleNamespace(), 'none'),
], ids=['nemotron3_super', 'nemotron_h_tiny', 'no_state'])
def test_stats_names_the_state_update_route(model, on_a_tpu, monkeypatch):
    """/stats `ssm_update_impl` is the route the model's decode round
    resolves to: the loop off a TPU and for a refused shape, the kernel
    on a TPU at the published widths, 'none' without state by slot."""
    engine = types.SimpleNamespace(model=model, mesh=None, num_slots=128)
    route = ContinuousBatchingEngine.ssm_update_impl
    assert route(engine) == ('none' if on_a_tpu == 'none'
                             else 'live_rows_loop')
    monkeypatch.setattr(ps, 'available', lambda: True)
    assert route(engine) == on_a_tpu
