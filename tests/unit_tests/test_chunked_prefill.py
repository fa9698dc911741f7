"""Stall-free serving scheduler: chunked prefill under a token budget
+ one-step host/device decode pipelining (models/batching.py).

Contracts under test:
  (a) a long prompt's prefill splits into >= 2 fixed-size chunks with
      decode steps interleaved between them (no whole-prompt stall);
  (b) per-iteration prefill work never exceeds the configured token
      budget;
  (c) chunked prefill composes with prefix-cache partial hits and
      with page-pressure preemption;
  (d) pipelined decode is token-for-token identical to the
      unpipelined loop at temperature 0 — and chunked prefill is
      bit-identical to the legacy whole-prompt prefill path (paged
      AND dense).

The deterministic tests drive the scheduler by hand (engine.stop()
right after construction kills the scheduler thread, the same idiom
as test_spec_batching's cancel-sweep test), so chunk/decode
interleaving is observable step by step instead of raced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from skypilot_tpu.models.batching import ContinuousBatchingEngine


@pytest.fixture(scope='module')
def llama_tiny():
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=8,
                           kv_total_pages=40)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    return model, params


PROMPTS = [
    [5, 9, 2, 5, 9, 2, 5, 9],
    [3, 3, 3, 3],
    [17, 41, 7, 29, 23, 5],
]
LONG_PROMPT = list(range(2, 42))        # 40 tokens = 5 chunks of 8


def _drain(eng):
    futs = [eng.submit(p, max_new_tokens=8) for p in PROMPTS]
    return [f.result(timeout=300) for f in futs]


# -- (a) chunk splitting + interleaving (hand-driven scheduler) ----------


def test_long_prompt_prefills_in_chunks_with_decode_interleaved(
        llama_tiny):
    model, params = llama_tiny
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=96,
                                   prefill_chunk=8,
                                   pipeline_decode=False)
    eng.stop()  # freeze the scheduler thread: we drive it by hand
    short = [5, 9, 2, 17]
    f_short = eng.submit(short, max_new_tokens=16)
    f_long = eng.submit(LONG_PROMPT, max_new_tokens=4)
    assert eng._admit()
    # Both slots admitted: short first (FCFS), both PREFILLING, no
    # device work yet.
    assert eng.prefilling.sum() == 2 and not eng.active.any()
    assert eng.prefill_backlog_tokens() == len(short) + len(LONG_PROMPT)

    # Iteration 1: the budget (= one 8-token chunk) covers the short
    # prompt only; the long prompt hasn't started.
    eng._prefill_work()
    assert eng.active[0] and not eng.active[1]
    assert eng.last_prefill_tokens == len(short)
    assert int(eng.prefill_frontier[1]) == 0

    # Drive iterations: each runs ONE 8-token chunk of the long
    # prompt, and the short prompt's decode commits tokens BETWEEN
    # chunks — the stall-free property.
    chunk_ends = []
    generated_between = []
    while eng.prefilling[1]:
        before = len(eng.outputs[0]) - len(short)
        eng._prefill_work()
        eng._decode_step()
        chunk_ends.append(int(eng.prefill_frontier[1]))
        generated_between.append(len(eng.outputs[0]) - len(short) -
                                 before)
    assert chunk_ends == [8, 16, 24, 32, 40]    # 5 chunks, >= 2
    # Decode made progress during every gap between chunks.
    assert all(g >= 1 for g in generated_between)
    assert eng.prefill_chunks_run >= 6          # 1 short + 5 long
    assert eng.prefill_backlog_tokens() == 0
    # Both requests complete when the loop keeps running.
    while eng.active.any():
        eng._decode_step()
    assert f_short.result(timeout=5)[:len(short)] == short
    long_out = f_long.result(timeout=5)
    assert long_out[:len(LONG_PROMPT)] == LONG_PROMPT
    assert len(long_out) == len(LONG_PROMPT) + 4


# -- (b) token-budget accounting ----------------------------------------


def test_prefill_budget_is_never_exceeded(llama_tiny):
    model, params = llama_tiny
    eng = ContinuousBatchingEngine(model, params, num_slots=4,
                                   max_total_len=96,
                                   prefill_chunk=8, prefill_budget=12,
                                   pipeline_decode=False)
    eng.stop()
    futs = [eng.submit(list(range(2, 2 + n)), max_new_tokens=2)
            for n in (20, 24, 28, 16)]
    eng._admit()
    total = sum((20, 24, 28, 16))
    spent = 0
    iterations = 0
    while any(eng.prefilling):
        eng._prefill_work()
        # THE budget contract: no iteration runs more prefill tokens
        # than configured.
        assert eng.last_prefill_tokens <= 12
        spent += eng.last_prefill_tokens
        eng._decode_step()
        iterations += 1
        assert iterations < 100
    assert spent == total  # every suffix token ran exactly once
    while eng.active.any():
        eng._decode_step()
    for f, n in zip(futs, (20, 24, 28, 16)):
        assert len(f.result(timeout=5)) == n + 2

    with pytest.raises(ValueError, match='prefill_budget'):
        ContinuousBatchingEngine(model, params, max_total_len=96,
                                 prefill_chunk=16, prefill_budget=8)


# -- (c) composition: prefix cache + page pressure -----------------------


def test_chunked_prefill_composes_with_prefix_cache(llama_tiny):
    """Partial prefix-cache hits leave a mid-prompt offset; chunked
    prefill must resume exactly there with identical outputs and the
    same hit/miss accounting as the whole-suffix path."""
    model, params = llama_tiny
    sys_prompt = list(range(2, 34))     # 4 full 8-token pages

    def run(**kw):
        eng = ContinuousBatchingEngine(model, params, num_slots=4,
                                       max_total_len=96, **kw)
        assert eng.paged and eng.prefix_cache is not None
        outs = []
        for extra in ([40, 41], [50, 51, 52], [60], [40, 41, 99]):
            outs.append(eng.submit(sys_prompt + extra,
                                   max_new_tokens=6).result(timeout=300))
        stats = (eng.prefix_cache.hits, eng.prefix_cache.misses)
        eng.stop()
        return outs, stats

    legacy, legacy_stats = run(prefill_chunk=0, pipeline_decode=False)
    chunked, chunked_stats = run(prefill_chunk=8)
    assert chunked == legacy
    assert chunked_stats == legacy_stats == (12, 4)


def test_chunked_prefill_composes_with_page_pressure():
    """A pool too small for all slots still serves every request with
    chunked prefill on: preemption re-queues and re-prefills (now in
    chunks) instead of failing."""
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_page_size=4,
                           kv_total_pages=16)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    eng = ContinuousBatchingEngine(model, params, num_slots=3,
                                   max_total_len=28, prefill_chunk=4)
    assert eng.paged
    try:
        futs = [eng.submit(p, max_new_tokens=18) for p in PROMPTS]
        rows = [f.result(timeout=300) for f in futs]
    finally:
        eng.stop()
    for p, row in zip(PROMPTS, rows):
        assert row[:len(p)] == p
        assert len(row) == len(p) + 18
    assert eng.preemptions >= 1     # the pool really was too small


# -- (d) output identity --------------------------------------------------


@pytest.mark.parametrize('paged', [None, False])
def test_pipelined_decode_identical_to_unpipelined(llama_tiny, paged):
    model, params = llama_tiny

    def run(pipeline):
        eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                       max_total_len=64, paged=paged,
                                       pipeline_decode=pipeline)
        assert eng.pipeline_decode is pipeline
        try:
            return _drain(eng)
        finally:
            eng.stop()

    assert run(True) == run(False)


@pytest.mark.parametrize('paged', [None, False])
def test_chunked_prefill_identical_to_whole_prompt(llama_tiny, paged):
    """Acceptance: temperature-0 outputs are bit-identical between the
    legacy whole-prompt prefill and chunked prefill, on the paged AND
    dense cache paths (dense exercises the new _dense_suffix_fn)."""
    model, params = llama_tiny
    prompts = PROMPTS + [LONG_PROMPT]

    def run(**kw):
        eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                       max_total_len=64, paged=paged,
                                       **kw)
        try:
            futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            return [f.result(timeout=300) for f in futs]
        finally:
            eng.stop()

    whole = run(prefill_chunk=0, pipeline_decode=False)
    for chunk in (8, 16):
        assert run(prefill_chunk=chunk) == whole


def test_pipeline_rejects_multi_token_decode_modes(llama_tiny):
    model, params = llama_tiny
    with pytest.raises(ValueError, match='pipeline_decode'):
        ContinuousBatchingEngine(model, params, max_total_len=48,
                                 speculative_k=2, pipeline_decode=True)
    with pytest.raises(ValueError, match='pipeline_decode'):
        ContinuousBatchingEngine(model, params, max_total_len=48,
                                 decode_chunk=4, pipeline_decode=True)
    # Auto mode: pipelining turns itself off for those engines.
    eng = ContinuousBatchingEngine(model, params, max_total_len=48,
                                   speculative_k=2)
    assert eng.pipeline_decode is False
    eng.stop()
    eng = ContinuousBatchingEngine(model, params, max_total_len=48)
    assert eng.pipeline_decode is True
    eng.stop()


def test_chunk_that_splits_pages_writes_token_wise(llama_tiny):
    """Only chunks of whole pages may promise the KV write a
    page-aligned start (page_aligned in the suffix dispatch): a
    prefill_chunk that splits pages (12 tokens, pages of 8) leaves the
    promise out, and its outputs equal the whole-prompt prefill's."""
    model, params = llama_tiny

    def run(chunk):
        eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                       max_total_len=64,
                                       prefill_chunk=chunk)
        aligned = eng._suffix_page_aligned  # pylint: disable=protected-access
        try:
            futs = [eng.submit(p, max_new_tokens=4)
                    for p in PROMPTS + [LONG_PROMPT]]
            return aligned, [f.result(timeout=300) for f in futs]
        finally:
            eng.stop()

    assert run(12) == (False, run(0)[1])
    assert run(16)[0] is True


def test_cancel_mid_prefill_resolves_with_prompt(llama_tiny):
    """A request cancelled while still PREFILLING resolves with its
    prompt, frees the slot, and never poisons the prefix cache with
    half-written pages."""
    model, params = llama_tiny
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=96, prefill_chunk=8,
                                   pipeline_decode=False)
    eng.stop()
    fut = eng.submit(LONG_PROMPT, max_new_tokens=4)
    eng._admit()
    eng._prefill_work()                  # one 8-token chunk only
    assert eng.prefilling[0] and not eng.active[0]
    eng.cancel([fut])
    eng._apply_cancellations()
    assert fut.result(timeout=5) == LONG_PROMPT
    assert not eng.prefilling[0] and not eng.active[0]
    assert not eng._prefill_order
    # Half-prefilled prompt pages were NOT promoted into the cache.
    assert len(eng.prefix_cache.by_key) == 0
    # The slot serves a fresh request end to end.
    fut2 = eng.submit(PROMPTS[0], max_new_tokens=3)
    eng._admit()
    eng._prefill_work()
    while eng.active.any():
        eng._decode_step()
    assert len(fut2.result(timeout=5)) == len(PROMPTS[0]) + 3
