"""Pallas paged-attention + QKV LoRA kernels
(ops/pallas_paged.py), interpret mode on CPU.

Five contracts:

  - PARITY MATRIX: the interpret-mode kernel matches the XLA
    reference over {bf16-style, int8} x {GQA divisible, GQA
    remainder} x {decode S=1, chunk S>1} shapes, and the fused QKV
    LoRA kernel matches lora.apply_delta bit-for-bit;
  - NON-VACUITY: a deliberately perturbed kernel FAILS the same pin
    (the PR 15 collective-guard discipline — a pin that cannot fail
    proves nothing);
  - DISPATCH: resolve_impl's rules (the backend and the pool's static
    shape, nothing a user sets), impl_scope, a missing forced route
    raising instead of degrading to 'xla', and unavailable_reason;
  - DECODE READ (`paged_decode_kernel`, route 'decode'): parity over
    the head shapes the repo serves at lengths around a block's
    edges, rows of length 0 that read no page, the perturbed control,
    the static-shape route rules and the shard map under a tensor
    mesh;
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
    ref = pa._reference_chunk_attention(q, k, v, positions, tbl,
                                        k_scales=ks, v_scales=vs)
    out = pp.fused_paged_attention(q, k, v, positions, tbl,
                                   k_scales=ks, v_scales=vs,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=ATOL)


def test_dispatch_entrypoints_route_to_fused():
    """paged_decode_attention itself picks the fused kernel under
    `impl_scope('fused_interpret')` (same numbers as the explicit
    call — the integration llama/gpt decode uses)."""
    batch = 4
    tbl, k, v, ks, vs = _paged_inputs(batch, 2, 1, True)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((batch, 4, D)), jnp.float32)
    lengths = jnp.asarray([1, 7, 20, 32], jnp.int32)
    ref = pa._reference_paged_attention(q, k, v, lengths, tbl,
                                        k_scales=ks, v_scales=vs)
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


# -- the decode read: one copy a page, all heads ----------------------------
DPAGE, DPSEQ = 16, 8          # 128 tokens a row
DBLOCK_PAGES = 2              # the tests' block: 32 tokens


def _decode_inputs(hkv, hq, d, lengths, dtype, seed=5):
    """A pool whose page 0 (the engine's trash page) is NaN, and a
    table that names real pages only below ceil(length / page): every
    other entry, and every entry of a row of length 0, is the trash
    page, as the engine leaves them. Returns (q, k, v, lengths,
    table) and the same pool with the trash page zeroed, for the
    reference (which gathers the whole table)."""
    batch = len(lengths)
    total = batch * DPSEQ + 1
    rng = np.random.default_rng(seed)
    shape = (hkv, total, DPAGE, d)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((batch, hq, d)), dtype)
    perm = (rng.permutation(total - 1) + 1).reshape(batch, DPSEQ)
    live = -(-np.asarray(lengths) // DPAGE)
    tbl = np.where(np.arange(DPSEQ)[None] < live[:, None], perm, 0)
    k_nan, v_nan = k.copy(), v.copy()
    k_nan[:, 0] = v_nan[:, 0] = np.nan
    k[:, 0] = v[:, 0] = 0.0
    as_pool = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    return ((q, as_pool(k_nan), as_pool(v_nan),
             jnp.asarray(lengths, jnp.int32), jnp.asarray(tbl, jnp.int32)),
            (as_pool(k), as_pool(v)))


def _small_blocks(monkeypatch, hkv, d, dtype):
    """Steer the block to DBLOCK_PAGES so that a 128-token row is
    four blocks (the budget is the one static input a test can move
    without a chip-sized pool)."""
    page_bytes = hkv * DPAGE * d * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(pp, '_DECODE_VMEM_BUDGET',
                        4 * DBLOCK_PAGES * page_bytes)
    pool = jax.ShapeDtypeStruct((hkv, 9, DPAGE, d), dtype)
    assert pp.decode_block_pages(pool, DPSEQ) == DBLOCK_PAGES


DECODE_EDGE_LENGTHS = [0, 1, 31, 32, 33, 128, 0, 101]


@pytest.mark.parametrize(
    'hkv,hq,d,dtype,atol',
    [(8, 32, 128, jnp.float32, ATOL),
     (2, 8, 128, jnp.float32, ATOL),
     (4, 4, 128, jnp.float32, ATOL),
     (8, 32, 128, jnp.bfloat16, 2e-2)],
    ids=['mistral_8x4', 'tensor4_slice_2x4', 'group1_4x1',
         'mistral_8x4_bf16'])
def test_decode_kernel_parity(monkeypatch, hkv, hq, d, dtype, atol):
    """Lengths 0, 1, one under / exactly / one over a block, the full
    table and a ragged one, against the XLA reference; rows of length
    0 return zeros and NO row reads a page past its length: the trash
    page behind every unused table entry holds NaN."""
    _small_blocks(monkeypatch, hkv, d, dtype)
    args, (k, v) = _decode_inputs(hkv, hq, d, DECODE_EDGE_LENGTHS, dtype)
    q, _, _, lengths, tbl = args
    out = np.asarray(pp.paged_decode_kernel(*args, interpret=True),
                     np.float32)
    assert out.dtype == np.float32 and np.all(np.isfinite(out))
    ref = np.asarray(pa._reference_paged_attention(
        q, k, v, jnp.maximum(lengths, 1), tbl), np.float32)
    live = np.asarray(lengths) > 0
    assert not np.any(out[~live])
    np.testing.assert_allclose(out[live], ref[live], atol=atol,
                               rtol=atol if atol > ATOL else 0)


def test_decode_kernel_output_dtype_and_budgeted_block():
    """At the budget the code states, a bf16 pool of 8 heads walks a
    row in whole-table blocks here (8 pages < the budget's), returns
    q's dtype, and an all-dead batch starts no copy at all."""
    args, (k, v) = _decode_inputs(8, 32, 128, [77, 0, 128],
                                  jnp.bfloat16)
    assert pp.decode_block_pages(args[1], DPSEQ) == DPSEQ
    out = pp.paged_decode_kernel(*args, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = pa._reference_paged_attention(args[0], k, v,
                                        jnp.maximum(args[3], 1), args[4])
    np.testing.assert_allclose(np.asarray(out, np.float32)[[0, 2]],
                               np.asarray(ref, np.float32)[[0, 2]],
                               atol=2e-2, rtol=2e-2)
    dead = pp.paged_decode_kernel(args[0], args[1], args[2],
                                  jnp.zeros((3,), jnp.int32),
                                  jnp.zeros_like(args[4]),
                                  interpret=True)
    assert not np.any(np.asarray(dead, np.float32))


def test_perturbed_decode_kernel_fails_the_pin(monkeypatch):
    """Non-vacuity control for the decode read's pin."""
    _small_blocks(monkeypatch, 2, 128, jnp.float32)
    args, (k, v) = _decode_inputs(2, 8, 128, [1, 33, 128], jnp.float32)
    ref = pa._reference_paged_attention(args[0], k, v, args[3], args[4])
    good = pp.paged_decode_kernel(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(good), np.asarray(ref),
                               atol=ATOL)
    bad = pp.paged_decode_kernel(*args, interpret=True, perturb=0.5)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(bad), np.asarray(ref),
                                   atol=ATOL)


@pytest.mark.parametrize(
    'hkv,page,d,dtype,pages_per_seq,want',
    [(8, 16, 128, jnp.bfloat16, 128, 32),    # the benchmark's cells
     (2, 16, 128, jnp.bfloat16, 128, 128),   # their --tensor 4 slice
     (8, 16, 128, jnp.bfloat16, 4, 4),       # never past the table
     (8, 16, 128, jnp.float32, 128, 16),
     (64, 64, 256, jnp.float32, 128, 1)],    # never under one page
    ids=['8_heads', '2_heads', 'short_table', 'f32', 'huge_page'])
def test_decode_block_follows_the_static_shapes(hkv, page, d, dtype,
                                                pages_per_seq, want):
    pool = jax.ShapeDtypeStruct((hkv, 9, page, d), dtype)
    assert pp.decode_block_pages(pool, pages_per_seq) == want


@pytest.mark.parametrize(
    'page,d,dtype,why',
    [(16, 128, jnp.bfloat16, None), (8, 128, jnp.float32, None),
     (16, 256, jnp.bfloat16, None),
     (16, 64, jnp.bfloat16, '128 lanes'),    # GPT-2's heads
     (8, 128, jnp.bfloat16, '16-sublane'),   # half a bf16 tile
     (32, 128, jnp.int8, 'int8')],
    ids=['bf16_16x128', 'f32_8x128', 'bf16_16x256', 'd64', 'page8_bf16',
         'int8'])
def test_decode_kernel_refusal_rules(page, d, dtype, why):
    got = pp.decode_kernel_refusal(
        jax.ShapeDtypeStruct((2, 3, page, d), dtype))
    assert (got is None) if why is None else (why in got)
    if why is not None and dtype != jnp.int8:
        pool = jnp.zeros((2, 3, page, d), dtype)
        with pytest.raises(ValueError, match="impl 'decode'"):
            pp.paged_decode_kernel(
                jnp.zeros((1, 2, d), dtype), pool, pool,
                jnp.ones((1,), jnp.int32), jnp.zeros((1, 2), jnp.int32),
                interpret=True)


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


# -- dispatch resolution ----------------------------------------------------
def test_resolve_impl_cpu_rules():
    # CPU: the backend is observed and the XLA reference taken,
    # whatever the pool; the interpret route runs anywhere, through
    # the scope.
    bf16_pool = jax.ShapeDtypeStruct((8, 64, 16, 128), jnp.bfloat16)
    assert pp.resolve_impl(quantized=True) == 'xla'
    assert pp.resolve_impl(quantized=False) == 'xla'
    assert pp.resolve_impl(decode_pool=bf16_pool) == 'xla'
    with pp.impl_scope('xla'):
        assert pp.resolve_impl(quantized=True) == 'xla'
    with pp.impl_scope('fused_interpret'):
        assert pp.resolve_impl() == 'fused_interpret'
    with pytest.raises(ValueError):
        with pp.impl_scope('bogus'):
            pass
    with pytest.raises(ValueError):
        with pp.impl_scope('kernel'):       # the upstream route: gone
            pass
    assert 'kernel' not in pp.IMPLS and 'auto' not in pp.IMPLS


def test_resolve_impl_raises_when_selected_route_is_missing():
    """A route forced BY NAME that cannot run is an error, never a
    quiet 'xla': on the chip that switch is what would let a kernel
    Mosaic refused go unnoticed behind a server that still answers."""
    for impl in ('decode', 'fused'):
        with pp.impl_scope(impl):
            with pytest.raises(ValueError, match='cannot run'):
                pp.resolve_impl()
    # The decode read takes unquantized pools only — also an error,
    # on any backend, instead of a degrade.
    with pp.impl_scope('decode'):
        with pytest.raises(ValueError, match='unquantized pools only'):
            pp.resolve_impl(quantized=True)


def test_resolve_impl_tpu_rules(monkeypatch):
    """What a read takes where the compiled routes exist (the backend
    is the one thing faked: CPU tests cannot have a TPU)."""
    monkeypatch.setattr(pp.jax, 'default_backend', lambda: 'tpu')
    assert pp.unavailable_reason() is None
    # The decode read hands its K pool over; what the kernel takes is
    # its static shape (a page of one head in whole tiles).
    pool = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16)
    assert pp.resolve_impl(decode_pool=pool(
        8, 5120, 16, 128)) == 'decode'           # Mistral, Llama
    assert pp.resolve_impl(decode_pool=pool(
        2, 5120, 16, 128)) == 'decode'           # their --tensor 4 slice
    assert pp.resolve_impl(decode_pool=pool(
        12, 512, 16, 64)) == 'xla'               # GPT-2: 64-wide heads
    assert pp.resolve_impl(decode_pool=pool(
        8, 512, 8, 128)) == 'xla'                # half-tile pages
    # An S>1 chunk read passes no pool: the gather, under its name.
    assert pp.resolve_impl(quantized=False) == 'xla'
    assert pp.resolve_impl(quantized=True) == 'fused'
    assert pp.resolve_impl(quantized=True, decode_pool=jax.
                           ShapeDtypeStruct((8, 64, 32, 128),
                                            jnp.int8)) == 'fused'
    # Forced through the scope a compiled route is what is named,
    # whatever the observations would give.
    with pp.impl_scope('fused'):
        assert pp.resolve_impl(quantized=False) == 'fused'
    with pp.impl_scope('decode'):
        assert pp.resolve_impl() == 'decode'
    assert pp.lora_fusion_impl(quantized=True) == 'fused'
    assert pp.lora_fusion_impl(quantized=False) is None


def test_env_and_scope_overrides(monkeypatch):
    """The scope is the one override, and it unwinds; the environment
    variable the ladder used to read changes nothing."""
    monkeypatch.setenv('SKYPILOT_TPU_PAGED_IMPL', 'fused_interpret')
    assert pp.resolve_impl(quantized=True) == 'xla'
    assert pp.lora_fusion_impl() is None
    with pp.impl_scope('fused_interpret'):
        assert pp.resolve_impl() == 'fused_interpret'
        assert pp.lora_fusion_impl() == 'fused_interpret'
        with pp.impl_scope('xla'):
            assert pp.resolve_impl(quantized=True) == 'xla'
        assert pp.resolve_impl() == 'fused_interpret'
    assert pp.resolve_impl() == 'xla'
    assert pp.lora_fusion_impl() is None


def test_chunk_read_of_a_bf16_pool_is_the_gather_on_a_tpu(monkeypatch):
    """With the compiled routes there (the backend faked as a TPU), an
    S>1 chunk of an unquantized pool traces the XLA gather and no
    Pallas call, and `resolve_impl` calls that read 'xla': one name,
    one program."""
    monkeypatch.setattr(pp.jax, 'default_backend', lambda: 'tpu')
    assert pp.resolve_impl(quantized=False) == 'xla'
    batch, chunk, hkv, hq, hd, page = 2, 4, 2, 4, 128, 16
    pool = jax.ShapeDtypeStruct((hkv, 9, page, hd), jnp.bfloat16)
    rest = (jax.ShapeDtypeStruct((batch, chunk), jnp.int32),    # positions
            jax.ShapeDtypeStruct((batch, 4), jnp.int32))        # table
    q = jax.ShapeDtypeStruct((batch, chunk, hq, hd), jnp.bfloat16)
    text = str(jax.make_jaxpr(pa.paged_chunk_attention)(
        q, pool, pool, *rest))
    assert 'pallas_call' not in text and 'gather' in text
    # The same read of an int8 pool is the fused kernel.
    scales = jax.ShapeDtypeStruct((9, page), jnp.float32)
    int8_pool = jax.ShapeDtypeStruct(pool.shape, jnp.int8)
    fused = jax.make_jaxpr(pa.paged_chunk_attention)(
        q, int8_pool, int8_pool, *rest, scales, scales)
    assert 'pallas_call' in str(fused)


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


def test_decode_kernel_is_shard_mapped_under_a_tensor_mesh(monkeypatch):
    """The route a bf16 pool of 128-wide heads takes on a TPU, under
    --tensor N: the call must be shard_mapped over kv heads, or GSPMD
    — to which a Pallas call is opaque — gathers the head-sharded pool
    onto every chip, each layer, each step. The in-repo decode read
    runs per chip on its own kv-head slice (in the TPU interpreter,
    the one way a CPU can execute its DMAs), and the engine-facing
    wrapper picks it from the pool's static shape."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import NamedSharding, PartitionSpec as P
    from skypilot_tpu.parallel import mesh as mesh_lib
    if len(jax.devices()) < 2:
        pytest.skip('needs >= 2 host devices')
    monkeypatch.setattr(pp, 'available', lambda: True)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    args, (k, v) = _decode_inputs(2, 8, 128, [37, 0, 128],
                                  jnp.bfloat16)
    q, k_nan, v_nan, lengths, tbl = args
    ref = pa._reference_paged_attention(q, k, v,
                                        jnp.maximum(lengths, 1), tbl)
    pool = NamedSharding(mesh, P('tensor'))
    heads = NamedSharding(mesh, P(None, 'tensor', None))
    placed = (jax.device_put(q, heads), jax.device_put(k_nan, pool),
              jax.device_put(v_nan, pool), lengths, tbl)
    fn = jax.jit(lambda *a: pa.paged_decode_attention(*a))
    with pltpu.force_tpu_interpret_mode(), mesh:
        out = fn(*placed)
        hlo = fn.lower(*placed).compile().as_text()
    assert out.sharding.spec == P(None, 'tensor', None)
    assert 'all-gather' not in hlo and 'all-to-all' not in hlo
    out = np.asarray(out, np.float32)
    assert np.all(np.isfinite(out)) and not np.any(out[1])
    np.testing.assert_allclose(out[[0, 2]],
                               np.asarray(ref, np.float32)[[0, 2]],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize('heads,page,want',
                         [(1, 16, 'decode'),     # 128-wide heads
                          (2, 16, 'xla'),        # 64-wide heads
                          (1, 8, 'xla')],        # half-tile pages
                         ids=['d128', 'd64', 'page8'])
def test_engine_names_the_route_its_decode_holds(monkeypatch, heads,
                                                 page, want):
    """`attention_impl()` (what /stats and the gauge report) follows
    the pool's static shape as the traced decode does, where the
    compiled routes exist."""
    from skypilot_tpu.models.batching import ContinuousBatchingEngine
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig(vocab_size=64, max_seq_len=64, num_layers=1,
                      num_heads=heads, num_kv_heads=heads, embed_dim=128,
                      mlp_dim=128, dtype=jnp.bfloat16, kv_page_size=page,
                      kv_total_pages=16)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=64)
    try:
        assert eng.paged and eng.attention_impl() == 'xla'   # this CPU
        monkeypatch.setattr(pp, 'available', lambda: True)
        assert eng.attention_impl() == want
    finally:
        eng.stop()


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
