"""Quantized serving: int8 KV pages + per-channel int8 weights.

The contracts under test:

  - int8 KV page round-trips are exact for constant pages and
    bounded (scale/2 per element) otherwise; scales live in parallel
    scale pages and travel with their physical page.
  - Greedy decode under kv_dtype=int8 stays within the documented
    logprob tolerance of the bf16 path on the echo+logprobs scoring
    harness (the /v1/completions eval contract), and the scheduler
    invariants (pipelined == unpipelined, chunked-decode bit-
    identity, preempt/recover determinism) survive quantized
    storage.
  - Prefix-cache hits return QUANTIZED pages with their scales: a
    cache-hit continuation is bit-identical to recomputing the same
    pages fresh.
  - weight_dtype=int8 per-channel projections serve within tolerance
    of the f32 model, compose with batched LoRA (parity vs the
    merged-weights oracle) and with --tensor 2 on CPU host devices
    (bit-identical to the single-device int8 run).
"""
import os
import tempfile

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import quant as quant_lib
from skypilot_tpu.inference.adapters import AdapterRegistry
from skypilot_tpu.inference.runtime import InferenceRuntime
from skypilot_tpu.models import lora as lora_lib
from skypilot_tpu.models.batching import ContinuousBatchingEngine
from skypilot_tpu.models.llama import Llama, LlamaConfig
from skypilot_tpu.ops import paged_attention as paged_ops

#: Documented tolerance (docs/guides.md "Quantized serving"): mean
#: per-token logprob of a quantized greedy continuation, scored by
#: the exact (full-forward) scorer, within this of the bf16 path's.
LOGPROB_TOL = 0.1


def _build(kv_dtype='bf16', **kw):
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                           kv_total_pages=40, kv_dtype=kv_dtype, **kw)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    return model, params


@pytest.fixture(scope='module')
def base():
    return _build()


@pytest.fixture(scope='module')
def base_int8(base):
    """Same weights as `base`, int8 KV config."""
    model_q, _ = _build(kv_dtype='int8')
    return model_q, base[1]


class _IntTok:
    """Space-separated-int 'tokenizer': enough for the OpenAI
    completions contract functions on a registry model."""

    def __call__(self, prompt):
        return {'input_ids': [int(t) for t in prompt.split()]}

    def decode(self, ids, skip_special_tokens=True):
        return ' '.join(str(int(t)) for t in ids)

    def convert_ids_to_tokens(self, ids):
        return [str(int(t)) for t in ids]


def _runtime(model, params, engine) -> InferenceRuntime:
    rt = InferenceRuntime(
        model=model, params=params,
        vocab_size=model.config.vocab_size, model_name='llama-tiny',
        max_total_len=48, spec_total=48, speculative=0,
        engine=engine, engine_total=48)
    rt._tok_holder['tok'] = _IntTok()
    return rt


def _score_continuation(rt: InferenceRuntime, row, prompt_len: int
                        ) -> float:
    """Mean per-token logprob of row[prompt_len:] under rt's exact
    scorer — THE echo+logprobs quantity: /v1/completions with
    echo+logprobs reports exactly score_logprobs values."""
    lp = rt.score_logprobs(list(row))
    gen = [float(lp[i - 1, row[i]]) for i in
           range(prompt_len, len(row))]
    return sum(gen) / max(len(gen), 1)


# -- page round-trip --------------------------------------------------------
def test_constant_page_roundtrip_bit_exact():
    """A page of constant K/V values survives quantization exactly:
    absmax symmetric int8 maps c -> +/-127 -> c."""
    x = jnp.full((5, 2, 32), -3.25, jnp.float32)
    q, scale = paged_ops.quantize_kv_rows(x)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), -127)
    back = paged_ops.dequantize_kv(q, scale)
    np.testing.assert_array_equal(np.asarray(back), -3.25)


def test_write_kv_quant_roundtrip_bounded():
    """write -> gather -> dequant reproduces the written rows within
    scale/2 per element, with scales landing at the written page
    slots of the parallel scale array."""
    rng = np.random.default_rng(0)
    heads, pages, page, hd, batch = 2, 6, 8, 16, 3
    kp = jnp.zeros((heads, pages, page, hd), jnp.int8)
    vp = jnp.zeros_like(kp)
    ks = jnp.zeros((pages, page), jnp.float32)
    vs = jnp.zeros_like(ks)
    k_new = jnp.asarray(rng.normal(size=(batch, heads, hd)),
                        jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(batch, heads, hd)),
                        jnp.float32)
    positions = jnp.asarray([0, 9, 17], jnp.int32)
    table = jnp.asarray([[1, 2, 3], [2, 3, 4], [3, 4, 5]], jnp.int32)
    kp, vp, ks, vs = paged_ops.write_kv_quant(
        kp, vp, ks, vs, k_new, v_new, positions, table)
    ks_np = np.asarray(ks)
    # Rows wrote (physical page, slot) = (1,0), (3,1), (5,1).
    for b, (phys, slot) in enumerate([(1, 0), (3, 1), (5, 1)]):
        scale = ks_np[phys, slot]
        assert scale > 0
        got = np.asarray(kp)[:, phys, slot, :].astype(np.float32) * \
            scale
        want = np.asarray(k_new)[b]
        assert np.abs(got - want).max() <= scale / 2 + 1e-7
        assert scale == pytest.approx(
            np.abs(want).max() / 127.0, rel=1e-6)


def test_chunk_write_equals_tokenwise_write():
    """write_kv_chunk_quant == repeated write_kv_quant: per-token
    scales make chunked prefill and single-token decode write the
    SAME quantized bytes (what makes cache-hit continuations
    bit-identical to fresh computation)."""
    rng = np.random.default_rng(1)
    heads, pages, page, hd, S = 2, 5, 4, 8, 6
    k_new = jnp.asarray(rng.normal(size=(1, S, heads, hd)),
                        jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(1, S, heads, hd)),
                        jnp.float32)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]

    def fresh():
        return (jnp.zeros((heads, pages, page, hd), jnp.int8),
                jnp.zeros((heads, pages, page, hd), jnp.int8),
                jnp.zeros((pages, page), jnp.float32),
                jnp.zeros((pages, page), jnp.float32))

    chunked = paged_ops.write_kv_chunk_quant(
        *fresh(), k_new, v_new, positions, table)
    kp, vp, ks, vs = fresh()
    for s in range(S):
        kp, vp, ks, vs = paged_ops.write_kv_quant(
            kp, vp, ks, vs, k_new[:, s], v_new[:, s],
            positions[:, s], table)
    for a, b in zip(chunked, (kp, vp, ks, vs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#: name -> (positions [B, S], page table, page_aligned); page 4 tokens,
#: page 0 the trash page.
_INT8_WRITE_CASES = {
    'decode_rows': (np.asarray([[0], [3], [4], [10]]),
                    np.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9],
                                [10, 11, 12]]), False),
    'aligned_chunk_padded_tail': (4 + np.arange(12)[None, :],
                                  np.asarray([[5, 6, 0, 0]]), True),
    'unaligned_verify_chunk': (np.asarray([[2], [7]])
                               + np.arange(3)[None, :],
                               np.asarray([[1, 2, 3], [4, 5, 6]]),
                               False),
}


@pytest.mark.parametrize('case', sorted(_INT8_WRITE_CASES))
def test_int8_write_is_bit_identical_to_scatter_reference(case):
    """The in-place int8 write leaves pages AND scale pages holding
    exactly what the scatter form put there: the quantized rows of
    `quantize_kv_rows` at (page, slot) through the table, in (row,
    position) order, nothing else touched."""
    positions, table, aligned = _INT8_WRITE_CASES[case]
    batch, chunk = positions.shape
    heads, pages, page, hd = 2, 13, 4, 8
    rng = np.random.default_rng(7)
    pools = [jnp.asarray(rng.integers(-127, 128, (heads, pages, page,
                                                  hd)), jnp.int8)
             for _ in range(2)]
    scales = [jnp.asarray(rng.random((pages, page)), jnp.float32)
              for _ in range(2)]
    news = [jnp.asarray(rng.normal(size=(batch, chunk, heads, hd)),
                        jnp.float32) for _ in range(2)]
    pos = jnp.asarray(positions, jnp.int32)
    tbl = jnp.asarray(table, jnp.int32)
    if chunk == 1:
        got = jax.jit(paged_ops.write_kv_quant)(
            *pools, *scales, news[0][:, 0], news[1][:, 0], pos[:, 0],
            tbl)
    else:
        got = jax.jit(paged_ops.write_kv_chunk_quant,
                      static_argnames='page_aligned')(
            *pools, *scales, *news, pos, tbl, page_aligned=aligned)
    for i in range(2):
        q, sc = paged_ops.quantize_kv_rows(news[i])
        want_pool, want_scale = np.array(pools[i]), np.array(scales[i])
        for b in range(batch):
            for s in range(chunk):
                phys = table[b, positions[b, s] // page]
                slot = positions[b, s] % page
                want_pool[:, phys, slot, :] = np.asarray(q[b, s])
                want_scale[phys, slot] = np.asarray(sc[b, s])
        assert got[i].dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got[i]), want_pool)
        np.testing.assert_array_equal(np.asarray(got[2 + i]),
                                      want_scale)


# -- weight quantization ----------------------------------------------------
def test_weight_quantize_targets_and_bounds(base):
    """Only the projection kernels quantize (embeddings/norms/head
    untouched); per-output-channel dequant error is bounded by
    scale/2; a constant column round-trips exactly."""
    _, params = base
    q = quant_lib.quantize_params(params)
    attn = q['layer_0']['attn']
    for t in ('wq', 'wk', 'wv', 'wo'):
        assert attn[t]['kernel_q'].dtype == np.int8
        assert 'kernel' not in attn[t]
    for t in ('w_gate', 'w_up', 'w_down'):
        assert q['layer_0']['mlp'][t]['kernel_q'].dtype == np.int8
    assert q['tok_embed'].dtype == np.float32       # untouched
    assert q['lm_head'].dtype == np.float32
    assert 'kernel' not in q['final_norm']          # norm unchanged
    w = np.asarray(params['layer_0']['attn']['wq']['kernel'],
                   np.float32)
    scale = np.asarray(attn['wq']['kernel_scale'])
    back = attn['wq']['kernel_q'].astype(np.float32) * scale
    assert np.abs(back - w).max() <= scale.max() / 2 + 1e-7
    # Constant column: exact.
    w2 = np.full((4, 3), 0.5, np.float32)
    q2 = quant_lib.quantize_params({'wq': {'kernel': w2}})
    back2 = q2['wq']['kernel_q'].astype(np.float32) * \
        q2['wq']['kernel_scale']
    np.testing.assert_array_equal(back2, w2)


def test_quantized_model_wrapper_delegates(base):
    model, params = base
    qm = quant_lib.QuantizedModel(model)
    assert qm.config is model.config
    assert lora_lib.supports(qm)
    qparams = jax.tree.map(jnp.asarray,
                           quant_lib.quantize_params(params))
    toks = jnp.asarray([[5, 9, 2, 17]], jnp.int32)
    out_q = qm.apply({'params': qparams}, toks)
    out_f = model.apply({'params': params}, toks)
    assert out_q.shape == out_f.shape
    # Quantization noise is small but nonzero on random weights.
    assert not np.array_equal(np.asarray(out_q), np.asarray(out_f))
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                               atol=0.2)


# -- logprob-tolerance harness (the echo+logprobs contract) -----------------
def test_int8_kv_greedy_within_logprob_tolerance(base, base_int8):
    """Greedy continuations from the int8-KV engine score within
    LOGPROB_TOL of the bf16 engine's under the exact scorer (the
    quantity /v1/completions echo+logprobs reports)."""
    model, params = base
    model_q, _ = base_int8
    e_ref = ContinuousBatchingEngine(model, params, num_slots=2,
                                     max_total_len=48)
    e_q = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                   max_total_len=48)
    assert e_q.kv_dtype == 'int8' and e_q.paged
    rt = _runtime(model, params, e_ref)
    try:
        for prompt in ([5, 9, 2, 17], [30, 31, 32, 33, 34],
                       list(range(40, 60))):
            a = e_ref.submit(prompt, max_new_tokens=10).result(
                timeout=180)
            b = e_q.submit(prompt, max_new_tokens=10).result(
                timeout=180)
            lp_ref = _score_continuation(rt, a, len(prompt))
            lp_q = _score_continuation(rt, b, len(prompt))
            assert lp_q >= lp_ref - LOGPROB_TOL, (
                f'int8 KV continuation scores {lp_q:.4f} vs bf16 '
                f'{lp_ref:.4f} (tol {LOGPROB_TOL})')
    finally:
        e_ref.stop()
        e_q.stop()
        rt.stop()


def test_int8_kv_completions_echo_logprobs_endpoint(base_int8, base):
    """The actual /v1/completions scoring contract runs against an
    int8-KV runtime: echo+logprobs+max_tokens=0 returns finite
    per-token logprobs that match the bf16 runtime's exactly (the
    scorer is the cache-free full forward — quantized KV changes
    GENERATION, never scoring)."""
    from skypilot_tpu.inference.openai_compat import (
        CompletionRequest, run_completion)
    model, params = base
    model_q, _ = base_int8
    rt_q = _runtime(model_q, params, None)
    rt_f = _runtime(model, params, None)
    req = CompletionRequest(prompts=['5 9 2 17'], max_new=0,
                            temperature=0.0, top_p=1.0,
                            stop_strings=None, n=1, stream=False,
                            logprobs=0, echo=True)
    try:
        out_q = run_completion(rt_q, req)
        out_f = run_completion(rt_f, req)
        lp_q = out_q['choices'][0]['logprobs']['token_logprobs']
        lp_f = out_f['choices'][0]['logprobs']['token_logprobs']
        assert lp_q[0] is None and len(lp_q) == 4
        assert lp_q[1:] == pytest.approx(lp_f[1:], abs=1e-6)
    finally:
        rt_q.stop()
        rt_f.stop()


def test_int8_weights_within_logprob_tolerance(base):
    """weight_dtype=int8 greedy continuations score within tolerance
    of the f32 model's."""
    model, params = base
    qm = quant_lib.QuantizedModel(model)
    qparams = jax.tree.map(jnp.asarray,
                           quant_lib.quantize_params(params))
    e_ref = ContinuousBatchingEngine(model, params, num_slots=2,
                                     max_total_len=48)
    e_q = ContinuousBatchingEngine(qm, qparams, num_slots=2,
                                   max_total_len=48)
    rt = _runtime(model, params, e_ref)
    try:
        for prompt in ([5, 9, 2, 17], [7] * 12):
            a = e_ref.submit(prompt, max_new_tokens=10).result(
                timeout=180)
            b = e_q.submit(prompt, max_new_tokens=10).result(
                timeout=180)
            lp_ref = _score_continuation(rt, a, len(prompt))
            lp_q = _score_continuation(rt, b, len(prompt))
            assert lp_q >= lp_ref - LOGPROB_TOL
    finally:
        e_ref.stop()
        e_q.stop()
        rt.stop()


# -- scheduler invariants under int8 KV -------------------------------------
def test_pipelined_equals_unpipelined_int8(base_int8):
    """Greedy bit-identity of the pipelined decode loop survives
    quantized storage (both loops read the same quantized pages)."""
    model_q, params = base_int8
    outs = []
    for pipeline in (True, False):
        eng = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                       max_total_len=48,
                                       pipeline_decode=pipeline)
        try:
            outs.append([
                eng.submit(p, max_new_tokens=10).result(timeout=180)
                for p in ([5, 9, 2, 17], [30, 31, 32])])
        finally:
            eng.stop()
    assert outs[0] == outs[1]


def test_chunked_decode_bit_identical_int8(base_int8):
    """decode_chunk=4 == step-by-step under int8 KV (deterministic
    elementwise quantization keeps the scan/loop equivalence)."""
    model_q, params = base_int8
    outs = []
    for chunk in (1, 4):
        eng = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                       max_total_len=40,
                                       decode_chunk=chunk,
                                       pipeline_decode=False)
        try:
            outs.append(eng.submit([5, 9, 2, 17],
                                   max_new_tokens=12).result(
                timeout=180))
        finally:
            eng.stop()
    assert outs[0] == outs[1]


def test_speculative_decode_int8(base_int8):
    """Verify chunks ride quantized pages: speculative greedy output
    == plain greedy output (acceptance only commits model-confirmed
    tokens, and both paths read the same quantized history)."""
    model_q, params = base_int8
    prompt = [7, 8, 7, 8, 7, 8]
    outs = []
    for k in (0, 3):
        eng = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                       max_total_len=40,
                                       speculative_k=k)
        try:
            outs.append(eng.submit(prompt, max_new_tokens=10).result(
                timeout=180))
        finally:
            eng.stop()
    assert outs[0] == outs[1]


def test_chunked_prefill_preempt_recover_int8():
    """Chunked prefill + page-pressure preemption + re-admission all
    run under kv_dtype=int8, deterministically: two identical runs
    produce identical outputs and the pressured run preempts."""
    model_q, _ = _build(kv_dtype='int8')
    # A pool just big enough for one deep sequence: two concurrent
    # requests must preempt under page pressure.
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                           kv_total_pages=8, kv_dtype='int8')
    model_small = Llama(cfg)
    params = nn.meta.unbox(model_small.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])

    def run():
        eng = ContinuousBatchingEngine(model_small, params,
                                       num_slots=2, max_total_len=40,
                                       prefill_chunk=16,
                                       prefix_caching=False)
        try:
            futs = [eng.submit(list(range(2 + i, 22 + i)),
                               max_new_tokens=16) for i in range(2)]
            rows = [f.result(timeout=300) for f in futs]
            return rows, eng.preemptions
        finally:
            eng.stop()
    rows1, preempts1 = run()
    rows2, _ = run()
    assert preempts1 >= 1
    assert rows1 == rows2
    assert all(len(r) == 36 for r in rows1)


def test_prefix_cache_hit_returns_quantized_pages(base_int8):
    """A cache-hit continuation reads SHARED quantized pages + scales
    and is bit-identical to a fresh engine computing the same pages:
    the prefix cache shares int8 storage correctly (one copy, same
    refcounting, scales travel with the page)."""
    model_q, params = base_int8
    prefix = list(range(2, 34))          # 4 full pages of 8
    suffix = [40, 41, 42]
    e1 = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                  max_total_len=48, prefill_chunk=16)
    e2 = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                  max_total_len=48, prefill_chunk=16)
    try:
        e1.submit(prefix, max_new_tokens=4).result(timeout=180)
        hits_before = e1.prefix_cache.hits
        out_hit = e1.submit(prefix + suffix,
                            max_new_tokens=8).result(timeout=180)
        assert e1.prefix_cache.hits > hits_before
        out_fresh = e2.submit(prefix + suffix,
                              max_new_tokens=8).result(timeout=180)
        assert out_hit == out_fresh
    finally:
        e1.stop()
        e2.stop()


# -- LoRA composition -------------------------------------------------------
def test_int8_weights_with_lora_matches_merged_oracle(base):
    """Batched LoRA on a quantized base: the delta applies in f32 on
    top of the DEQUANTIZED projections, so the continuation scores
    within tolerance of the merged-weights f32 oracle (and the LoRA
    actually bites: adapter output != quantized-base output)."""
    model, params = base
    # A deliberately LOUD adapter (big alpha): the delta must flip
    # greedy tokens, or the base_out inequality below is vacuous.
    spec = lora_lib.LoraSpec(rank=4, alpha=64.0)
    lp = lora_lib.random_adapter_params(0, model.config, spec)
    tmp = tempfile.mkdtemp(prefix='quant_lora_')
    lora_lib.save_adapter(os.path.join(tmp, 'ad0'), lp, spec,
                          base_model='llama-tiny')
    qm = quant_lib.QuantizedModel(model)
    qparams = jax.tree.map(jnp.asarray,
                           quant_lib.quantize_params(params))
    reg = AdapterRegistry(tmp, qm, max_adapters=2)
    merged = lora_lib.merge_lora(params, lp, spec)
    e_oracle = ContinuousBatchingEngine(model, merged, num_slots=2,
                                        max_total_len=48)
    e_q = ContinuousBatchingEngine(qm, qparams, num_slots=2,
                                   max_total_len=48,
                                   adapter_store=reg)
    rt = _runtime(model, merged, e_oracle)
    prompt = [5, 9, 2, 17, 30]
    try:
        a = e_oracle.submit(prompt, max_new_tokens=10).result(
            timeout=180)
        b = e_q.submit(prompt, max_new_tokens=10,
                       adapter='ad0').result(timeout=180)
        base_out = e_q.submit(prompt, max_new_tokens=10).result(
            timeout=180)
        assert b != base_out            # the adapter changed decode
        lp_oracle = _score_continuation(rt, a, len(prompt))
        lp_q = _score_continuation(rt, b, len(prompt))
        assert lp_q >= lp_oracle - LOGPROB_TOL
    finally:
        e_oracle.stop()
        e_q.stop()
        rt.stop()


# -- tensor-parallel composition (CPU host devices) -------------------------
def test_int8_kv_tensor2_identical(base_int8):
    """Acceptance: int8 KV under --tensor 2 == the single-device int8
    run, token for token."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel.serving import shard_params_for_serving
    model_q, params = base_int8
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    tp = shard_params_for_serving(model_q, params, mesh)
    e_sd = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                    max_total_len=48)
    e_tp = ContinuousBatchingEngine(model_q, tp, num_slots=2,
                                    max_total_len=48)
    try:
        for p in ([5, 9, 2, 17], [30, 31, 32, 33, 34]):
            a = e_sd.submit(p, max_new_tokens=8).result(timeout=180)
            b = e_tp.submit(p, max_new_tokens=8).result(timeout=180)
            assert a == b
    finally:
        e_sd.stop()
        e_tp.stop()


def test_int8_weights_tensor2_scales_shard_and_match(base):
    """Quantized kernels place with the base kernel's sharding, the
    per-channel scales shard over the output-channel mesh axis, and
    serving is bit-identical to single-device int8."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    model, params = base
    qm = quant_lib.QuantizedModel(model)
    qparams = quant_lib.quantize_params(params)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    tp = quant_lib.shard_quantized_for_serving(qm, qparams, mesh)
    wq = tp['layer_0']['attn']['wq']
    assert 'tensor' in str(wq['kernel_q'].sharding.spec)
    assert str(wq['kernel_scale'].sharding.spec) == \
        "PartitionSpec('tensor',)"
    sd = jax.tree.map(jnp.asarray, qparams)
    e_sd = ContinuousBatchingEngine(qm, sd, num_slots=2,
                                    max_total_len=48)
    e_tp = ContinuousBatchingEngine(qm, tp, num_slots=2,
                                    max_total_len=48)
    try:
        for p in ([5, 9, 2, 17],):
            a = e_sd.submit(p, max_new_tokens=8).result(timeout=180)
            b = e_tp.submit(p, max_new_tokens=8).result(timeout=180)
            assert a == b
    finally:
        e_sd.stop()
        e_tp.stop()


def test_adapter_store_replicated_under_tensor2(base):
    """Satellite: the stacked adapter store places REPLICATED over
    the mesh (not left to default placement), and a LoRA request
    under --tensor 2 matches the single-device output exactly."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel.serving import shard_params_for_serving
    model, params = base
    spec = lora_lib.LoraSpec(rank=4, alpha=8.0)
    lp = lora_lib.random_adapter_params(1, model.config, spec)
    tmp = tempfile.mkdtemp(prefix='quant_tp_lora_')
    lora_lib.save_adapter(os.path.join(tmp, 'ad0'), lp, spec,
                          base_model='llama-tiny')
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=2),
                              devices=jax.devices()[:2])
    reg_sd = AdapterRegistry(tmp, model, max_adapters=2)
    reg_tp = AdapterRegistry(tmp, model, max_adapters=2, mesh=mesh)
    tp = shard_params_for_serving(model, params, mesh)
    e_sd = ContinuousBatchingEngine(model, params, num_slots=2,
                                    max_total_len=48,
                                    adapter_store=reg_sd)
    e_tp = ContinuousBatchingEngine(model, tp, num_slots=2,
                                    max_total_len=48,
                                    adapter_store=reg_tp)
    try:
        prompt = [5, 9, 2, 17]
        a = e_sd.submit(prompt, max_new_tokens=8,
                        adapter='ad0').result(timeout=180)
        b = e_tp.submit(prompt, max_new_tokens=8,
                        adapter='ad0').result(timeout=180)
        assert a == b
        # The store is explicitly replicated over BOTH mesh devices.
        stack = reg_tp.model_lora()['layers']
        leaf = stack['layer_0']['wq']['a']
        assert len(leaf.sharding.device_set) == 2
    finally:
        e_sd.stop()
        e_tp.stop()


# -- engine validation + observability --------------------------------------
def test_int8_requires_paged():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_dtype='int8',
                           kv_total_pages=0)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    with pytest.raises(ValueError, match='paged'):
        ContinuousBatchingEngine(model, params, num_slots=2,
                                 max_total_len=48)


def test_kv_pool_bytes_math_and_gauges(base, base_int8):
    """int8 halves+ the pool bytes at equal page count; the same
    byte budget buys >= 1.8x the pages (the bench acceptance ratio
    is deterministic geometry, not load-dependent); gauges render."""
    model, params = base
    model_q, _ = base_int8
    cfg_bf = model.config
    cfg_q = model_q.config
    bf16_cfg = LlamaConfig.tiny()            # bf16 storage dtype
    per_bf = quant_lib.kv_page_bytes(bf16_cfg, 'bf16')
    per_q = quant_lib.kv_page_bytes(bf16_cfg, 'int8')
    assert per_bf / per_q >= 1.8
    budget = 1 << 20
    assert quant_lib.pool_pages_for_bytes(bf16_cfg, 'int8', budget) \
        >= 1.8 * quant_lib.pool_pages_for_bytes(bf16_cfg, 'bf16',
                                                budget)
    e_bf = ContinuousBatchingEngine(model, params, num_slots=2,
                                    max_total_len=48)
    e_q = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                   max_total_len=48)
    try:
        assert 0 < e_q.kv_cache_bytes() < e_bf.kv_cache_bytes()
        e_q.update_metric_gauges()
        from skypilot_tpu.observability import REGISTRY
        text = REGISTRY.render()
        assert 'skypilot_serving_kv_pool_bytes' in text
        assert cfg_q.kv_dtype == 'int8' and cfg_bf.kv_dtype == 'bf16'
    finally:
        e_bf.stop()
        e_q.stop()


def test_stats_reports_storage(base_int8):
    """/stats carries the storage section + page-pool kv_dtype and
    pool bytes (what serve_bench scrapes into the A/B record)."""
    model_q, params = base_int8
    eng = ContinuousBatchingEngine(model_q, params, num_slots=2,
                                   max_total_len=48)
    rt = _runtime(model_q, params, eng)
    rt.kv_dtype = 'int8'
    try:
        assert rt.weight_bytes > 0
        assert eng.kv_dtype == 'int8'
        assert eng.kv_cache_bytes() > 0
    finally:
        eng.stop()
        rt.stop()
