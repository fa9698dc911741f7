"""Fused pallas paged-attention + QKV LoRA kernels
(ops/pallas_paged.py), interpret mode on CPU.

Four contracts:

  - PARITY MATRIX: the interpret-mode kernel matches the XLA
    reference over {bf16-style, int8} x {GQA divisible, GQA
    remainder} x {decode S=1, chunk S>1} shapes, and the fused QKV
    LoRA kernel matches lora.apply_delta bit-for-bit;
  - NON-VACUITY: a deliberately perturbed kernel FAILS the same pin
    (the PR 15 collective-guard discipline — a pin that cannot fail
    proves nothing);
  - DISPATCH: resolve_impl's auto rules, the $SKYPILOT_TPU_PAGED_IMPL
    override, impl_scope, a missing selected route raising instead of
    degrading to 'xla', and unavailable_reason;
  - BIT IDENTITY end to end: an int8 + active-LoRA engine on the
    fused interpret path emits byte-identical greedy tokens to the
    XLA engine, and the mesh-sharded (tensor-2 host devices) kernel
    equals the unsharded kernel exactly.
"""
import os
import tempfile

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import lora as lora_lib
from skypilot_tpu.ops import paged_attention as pa
from skypilot_tpu.ops import pallas_paged as pp

PAGE, PSEQ, TOTAL, D = 8, 4, 32, 16
ATOL = 1e-5


def _paged_inputs(batch, hkv, seed, quantized):
    """Random pool + a randomly-permuted page table (scattered
    physical pages — the layout the kernel must gather through)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(TOTAL)
    tbl = jnp.asarray(perm[:batch * PSEQ].reshape(batch, PSEQ),
                      jnp.int32)
    shape = (hkv, TOTAL, PAGE, D)
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.random((TOTAL, PAGE)) * 0.02, jnp.float32)
        vs = jnp.asarray(rng.random((TOTAL, PAGE)) * 0.02, jnp.float32)
    else:
        k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        ks = vs = None
    return tbl, k, v, ks, vs


# -- parity matrix: attention -----------------------------------------------
@pytest.mark.parametrize('quantized', [False, True],
                         ids=['bf16', 'int8'])
@pytest.mark.parametrize('hkv,hq', [(2, 4), (3, 6)],
                         ids=['gqa_divisible', 'gqa_remainder'])
def test_decode_parity(quantized, hkv, hq):
    batch = 4
    tbl, k, v, ks, vs = _paged_inputs(batch, hkv, 1, quantized)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((batch, hq, D)), jnp.float32)
    lengths = jnp.asarray([1, 7, 20, 32], jnp.int32)  # cross-page mix
    ref = pa._reference_paged_attention(q, k, v, lengths, tbl,
                                        k_scales=ks, v_scales=vs)
    out = pp.fused_paged_attention(
        q[:, None], k, v, (lengths - 1)[:, None], tbl,
        k_scales=ks, v_scales=vs, interpret=True)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL)


@pytest.mark.parametrize('quantized', [False, True],
                         ids=['bf16', 'int8'])
@pytest.mark.parametrize('hkv,hq', [(2, 4), (3, 6)],
                         ids=['gqa_divisible', 'gqa_remainder'])
def test_chunk_parity(quantized, hkv, hq):
    batch, chunk = 3, 5
    tbl, k, v, ks, vs = _paged_inputs(batch, hkv, 3, quantized)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((batch, chunk, hq, D)),
                    jnp.float32)
    positions = jnp.asarray(
        rng.integers(0, PSEQ * PAGE, (batch, chunk)), jnp.int32)
    ref = pa.paged_chunk_attention(q, k, v, positions, tbl,
                                   k_scales=ks, v_scales=vs,
                                   impl='xla')
    out = pp.fused_paged_attention(q, k, v, positions, tbl,
                                   k_scales=ks, v_scales=vs,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL)


def test_dispatch_entrypoints_route_to_fused():
    """paged_decode_attention / paged_chunk_attention themselves pick
    the fused kernel under impl='fused_interpret' (same numbers as the
    explicit call above — the integration llama/gpt decode uses)."""
    batch = 4
    tbl, k, v, ks, vs = _paged_inputs(batch, 2, 1, True)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((batch, 4, D)), jnp.float32)
    lengths = jnp.asarray([1, 7, 20, 32], jnp.int32)
    ref = pa.paged_decode_attention(q, k, v, lengths, tbl,
                                    k_scales=ks, v_scales=vs,
                                    impl='xla')
    out = pa.paged_decode_attention(q, k, v, lengths, tbl,
                                    k_scales=ks, v_scales=vs,
                                    impl='fused_interpret')
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL)
    with pp.impl_scope('fused_interpret'):
        auto = pa.paged_decode_attention(q, k, v, lengths, tbl,
                                         k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                               atol=ATOL)


def test_perturbed_kernel_fails_the_pin():
    """Non-vacuity control: a kernel with a deliberate temperature
    error must NOT pass the parity pin."""
    batch = 4
    tbl, k, v, ks, vs = _paged_inputs(batch, 2, 1, True)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((batch, 4, D)), jnp.float32)
    lengths = jnp.asarray([1, 7, 20, 32], jnp.int32)
    ref = pa._reference_paged_attention(q, k, v, lengths, tbl,
                                        k_scales=ks, v_scales=vs)
    bad = pp.fused_paged_attention(
        q[:, None], k, v, (lengths - 1)[:, None], tbl,
        k_scales=ks, v_scales=vs, interpret=True, perturb=0.5)[:, 0]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(bad), np.asarray(ref),
                                   atol=ATOL)


# -- parity matrix: fused QKV LoRA ------------------------------------------
def test_fused_qkv_lora_matches_apply_delta():
    rng = np.random.default_rng(7)
    n_adapters, rank, d_model, batch, chunk = 4, 3, 32, 3, 5
    d_q, d_kv = 48, 24

    def factors(d_out):
        return {'a': jnp.asarray(rng.standard_normal(
                    (n_adapters, d_model, rank)) * 0.02, jnp.float32),
                'b': jnp.asarray(rng.standard_normal(
                    (n_adapters, rank, d_out)) * 0.02, jnp.float32)}

    fq, fk, fv = factors(d_q), factors(d_kv), factors(d_kv)
    x = jnp.asarray(rng.standard_normal((batch, chunk, d_model)),
                    jnp.float32)
    ids = jnp.asarray([0, 2, 3], jnp.int32)
    scale = jnp.asarray(2.0, jnp.float32)
    dq, dk, dv = pp.fused_qkv_lora_delta(x, fq, fk, fv, ids,
                                         interpret=True)
    for f, d in ((fq, dq), (fk, dk), (fv, dv)):
        y = jnp.zeros((batch, chunk, f['b'].shape[-1]), jnp.float32)
        want = lora_lib.apply_delta(y, x, f, ids, scale)
        got = y + (scale * d).astype(y.dtype)
        # Same contraction order in f32 -> exact, not just close.
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert pp.qkv_lora_dispatches_per_layer('fused_interpret') == 1
    assert pp.qkv_lora_dispatches_per_layer('xla') == 3


# -- dispatch resolution ----------------------------------------------------
def test_resolve_impl_cpu_rules():
    # CPU: 'auto' observes the backend and takes the XLA reference;
    # the interpret route runs anywhere.
    assert pp.resolve_impl('auto', quantized=True) == 'xla'
    assert pp.resolve_impl('auto', quantized=False) == 'xla'
    assert pp.resolve_impl('xla', quantized=True) == 'xla'
    assert pp.resolve_impl('fused_interpret') == 'fused_interpret'
    with pytest.raises(ValueError):
        pp.resolve_impl('bogus')
    with pytest.raises(ValueError):
        pp.set_default_impl('bogus')


def test_resolve_impl_raises_when_selected_route_is_missing(monkeypatch):
    """A route selected BY NAME that cannot run is an error, never a
    quiet 'xla': on the chip that switch is what would let a kernel
    Mosaic refused go unnoticed behind a server that still answers."""
    for impl in ('kernel', 'fused'):
        with pytest.raises(ValueError, match='cannot run'):
            pp.resolve_impl(impl)
        monkeypatch.setenv(pp.ENV_VAR, impl)
        with pytest.raises(ValueError, match='cannot run'):
            pp.resolve_impl('auto')
        monkeypatch.delenv(pp.ENV_VAR)
    # The upstream kernel reads bf16 pools only — also an error, on
    # any backend, instead of the old degrade.
    with pytest.raises(ValueError, match='bf16 pools only'):
        pp.resolve_impl('kernel', quantized=True)


def test_resolve_impl_tpu_rules(monkeypatch):
    """What 'auto' picks where the compiled routes exist (the backend
    is the one thing faked: CPU tests cannot have a TPU)."""
    monkeypatch.setattr(pp.jax, 'default_backend', lambda: 'tpu')
    assert pp.unavailable_reason() is None
    assert pp.resolve_impl('auto', quantized=False) == 'kernel'
    assert pp.resolve_impl('auto', quantized=True) == 'fused'
    assert pp.resolve_impl('fused', quantized=False) == 'fused'
    assert pp.lora_fusion_impl(quantized=True) == 'fused'
    assert pp.lora_fusion_impl(quantized=False) is None


def test_env_and_scope_overrides(monkeypatch):
    monkeypatch.setenv(pp.ENV_VAR, 'fused_interpret')
    assert pp.resolve_impl('auto', quantized=True) == 'fused_interpret'
    monkeypatch.setenv(pp.ENV_VAR, 'nope')
    with pytest.raises(ValueError):
        pp.resolve_impl('auto')
    monkeypatch.delenv(pp.ENV_VAR)
    with pp.impl_scope('fused_interpret'):
        assert pp.resolve_impl('auto') == 'fused_interpret'
        assert pp.lora_fusion_impl() == 'fused_interpret'
    assert pp.default_impl() == 'auto'
    assert pp.lora_fusion_impl() is None


def test_reports_why_kernel_is_off():
    """The recorded reason (the /stats storage field and skip-message
    source)."""
    assert not pp.available()            # CPU test environment
    reason = pp.unavailable_reason()
    assert reason is not None and 'fused_interpret' in reason
    assert pp.unavailable_reason() == reason    # stable across calls


def test_bytes_per_token_model_fused_beats_xla_at_int8():
    common = dict(num_layers=2, num_kv_heads=2, num_q_heads=4,
                  head_dim=16, page_size=8, pages_per_seq=4,
                  kv_elem_bytes=1, quantized=True, weight_bytes=1000,
                  batch=4, lora_bytes_per_row=64)
    xla = pp.bytes_per_token_model(impl='xla', **common)
    fused = pp.bytes_per_token_model(impl='fused_interpret', **common)
    assert xla['dequant_materialize_bytes'] > 0
    assert fused['dequant_materialize_bytes'] == 0
    assert (fused['total_bytes_per_token']
            < xla['total_bytes_per_token'])
    # Identical terms everywhere but the materialization:
    assert fused['kv_pool_bytes'] == xla['kv_pool_bytes']
    assert fused['kv_scale_bytes'] == xla['kv_scale_bytes']


# -- mesh-sharded bit identity (PR 15 harness: host-device mesh) ------------
def test_mesh_sharded_kernel_bit_identical():
    """tensor-2 mesh context -> the kernel shard_maps kv-heads over
    `tensor`; outputs must equal the unsharded kernel EXACTLY (each
    shard runs the identical per-head program)."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    if len(jax.devices()) < 2:
        pytest.skip('needs >= 2 host devices')
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    batch = 3
    tbl, k, v, ks, vs = _paged_inputs(batch, 2, 11, True)
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((batch, 1, 4, D)), jnp.float32)
    pos = jnp.asarray([[0], [12], [31]], jnp.int32)
    ref = pp.fused_paged_attention(q, k, v, pos, tbl, k_scales=ks,
                                   v_scales=vs, interpret=True)
    with mesh:
        out = pp.fused_paged_attention(q, k, v, pos, tbl, k_scales=ks,
                                       v_scales=vs, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # GQA remainder layout (3 kv heads, tensor=2): replicated pool ->
    # the unsharded path must be taken (and still be correct).
    tbl3, k3, v3, _, _ = _paged_inputs(batch, 3, 13, False)
    q3 = jnp.asarray(rng.standard_normal((batch, 1, 6, D)), jnp.float32)
    ref3 = pp.fused_paged_attention(q3, k3, v3, pos, tbl3,
                                    interpret=True)
    with mesh:
        out3 = pp.fused_paged_attention(q3, k3, v3, pos, tbl3,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(out3), np.asarray(ref3))


def test_upstream_kernel_is_shard_mapped_under_a_tensor_mesh(monkeypatch):
    """The route the one-chip server takes on TPU (bf16 pool ->
    upstream kernel) under --tensor N: the call must be shard_mapped
    over kv heads like the in-repo kernel is, or GSPMD — to which a
    Pallas call is opaque — gathers the head-sharded pool onto every
    chip, each layer, each step. Runs the real upstream kernel in the
    TPU interpreter (the one way a CPU can execute its DMAs)."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import NamedSharding, PartitionSpec as P
    from skypilot_tpu.parallel import mesh as mesh_lib
    if len(jax.devices()) < 2:
        pytest.skip('needs >= 2 host devices')
    monkeypatch.setattr(pp, 'available', lambda: True)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    batch, hq, hkv, hd, page, total = 2, 8, 2, 128, 16, 9
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    k = jax.random.normal(keys[0], (hkv, total, page, hd), jnp.bfloat16)
    v = jax.random.normal(keys[1], (hkv, total, page, hd), jnp.bfloat16)
    q = jax.random.normal(keys[2], (batch, hq, hd), jnp.bfloat16)
    tbl = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    lengths = jnp.asarray([37, 64], jnp.int32)
    ref = pa.paged_decode_attention(q, k, v, lengths, tbl, impl='xla')
    pool = NamedSharding(mesh, P('tensor'))
    heads = NamedSharding(mesh, P(None, 'tensor', None))
    args = (jax.device_put(q, heads), jax.device_put(k, pool),
            jax.device_put(v, pool), lengths, tbl)
    fn = jax.jit(lambda *a: pa.paged_decode_attention(*a, impl='kernel'))
    with pltpu.force_tpu_interpret_mode(), mesh:
        out = fn(*args)
        hlo = fn.lower(*args).compile().as_text()
    assert out.sharding.spec == P(None, 'tensor', None)
    assert 'all-gather' not in hlo and 'all-to-all' not in hlo
    # bf16 operands, f32 accumulation on both sides.
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


# -- end-to-end engine bit identity (int8 KV + active LoRA) -----------------
SPEC = lora_lib.LoraSpec(rank=4, alpha=8.0)


@pytest.fixture(scope='module')
def int8_lora_setup():
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                           kv_total_pages=40, kv_dtype='int8')
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    tmp = tempfile.mkdtemp(prefix='pallas_paged_lora_')
    for i in range(2):
        lp = lora_lib.random_adapter_params(i, cfg, SPEC)
        for layer in lp.values():          # default deltas are ~1e-3:
            for tgt in layer.values():     # amplify so adapters
                tgt['b'] *= 60.0           # actually flip greedy tokens
        lora_lib.save_adapter(os.path.join(tmp, f'ad{i}'), lp, SPEC,
                              base_model='llama-tiny')
    return model, params, tmp


def _greedy_tokens(model, params, adapter_dir, impl):
    from skypilot_tpu.inference.adapters import AdapterRegistry
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    with pp.impl_scope(impl):
        reg = AdapterRegistry(adapter_dir, model, max_adapters=4)
        eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                       max_total_len=48,
                                       adapter_store=reg)
        assert eng.paged and eng.kv_dtype == 'int8'
        assert eng.attention_impl() == impl
        prompt = list(range(2, 18))
        futs = [eng.submit(prompt, max_new_tokens=6)]
        futs += [eng.submit(prompt, max_new_tokens=6,
                            adapter=f'ad{i}') for i in range(2)]
        out = [f.result(timeout=300) for f in futs]
        eng.stop()
        return out


def test_engine_greedy_bit_identity_int8_lora(int8_lora_setup):
    """The acceptance pin: fused interpret-mode engine == XLA engine,
    byte-identical greedy tokens, int8 KV + active multi-LoRA."""
    model, params, adapter_dir = int8_lora_setup
    fused = _greedy_tokens(model, params, adapter_dir,
                           'fused_interpret')
    xla = _greedy_tokens(model, params, adapter_dir, 'xla')
    assert fused == xla
    # Three genuinely different models in the round (base + 2
    # adapters) — identity is not vacuous agreement on one stream.
    assert len({tuple(t) for t in fused}) == 3
