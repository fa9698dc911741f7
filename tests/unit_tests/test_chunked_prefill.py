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


# -- (e) the first-token handoff ------------------------------------------
# The plain pipelined loop leaves a finished prompt's first token on
# the device for the next round (`_hand_first_tokens`); every other
# loop fetches it (`_sync_first_tokens`). Hand-driven, so that rounds
# and chunks pair up the same way in every mode and a sampled request
# meets the same `_rng` splits.

# (prompt, max_new_tokens): a prompt that ends on a chunk boundary
# (16 = 2 chunks of 8), one that does not, one of a single chunk, and
# a request that wants one token only.
HANDOFF_REQUESTS = [
    (list(range(2, 18)), 6),
    ([5, 9, 2, 5, 9, 2, 5, 9, 17, 41, 7], 5),
    ([3, 3, 3, 3], 7),
    ([17, 41, 7, 29, 23, 5], 1),
]


def _serve_by_hand(eng, requests, temperature=0.0):
    """Serve `requests` on an engine whose scheduler thread is
    stopped: admit all, then iterate as `_iterate` does, a prefill
    pass and a decode step (where a lane is active or a round in
    flight) an iteration."""
    futs = [eng.submit(p, max_new_tokens=n, temperature=temperature)
            for p, n in requests]
    eng._admit()
    assert not eng._ready           # every request holds a slot
    for _ in range(200):
        if not (eng._prefill_order or eng.active.any() or
                eng._inflight is not None):
            break
        if eng._prefill_order:
            eng._prefill_work()
        if eng.active.any() or eng._inflight is not None:
            eng._decode_step()
    return [f.result(timeout=5) for f in futs]


def _hand_driven(model, params, requests, temperature=0.0, **kw):
    kw.setdefault('num_slots', len(requests))
    kw.setdefault('max_total_len', 64)
    kw.setdefault('prefill_chunk', 8)
    eng = ContinuousBatchingEngine(model, params, **kw)
    eng.stop()
    return _serve_by_hand(eng, requests, temperature), eng


@pytest.mark.parametrize('temperature', [0.0, 0.9])
@pytest.mark.parametrize('paged', [None, False])
def test_deferred_first_token_identical_to_unpipelined(
        llama_tiny, paged, temperature):
    model, params = llama_tiny
    want, ref = _hand_driven(model, params, HANDOFF_REQUESTS,
                             temperature, paged=paged,
                             pipeline_decode=False)
    got, eng = _hand_driven(model, params, HANDOFF_REQUESTS,
                            temperature, paged=paged)
    assert eng.pipeline_decode and got == want
    for (prompt, n), row in zip(HANDOFF_REQUESTS, got):
        assert row[:len(prompt)] == prompt
        assert len(row) == len(prompt) + n
    if temperature:
        greedy, _ = _hand_driven(model, params, HANDOFF_REQUESTS,
                                 paged=paged)
        assert got != greedy        # the seed was really drawn from
    assert (eng.first_tokens_deferred, eng.first_tokens_synced) == \
        (len(HANDOFF_REQUESTS), 0)
    assert (ref.first_tokens_deferred, ref.first_tokens_synced) == \
        (0, len(HANDOFF_REQUESTS))
    if eng.paged:
        # Every page went back (the prefix cache keeps what it was
        # given; the rest is free again).
        assert eng.allocator.free_pages + len(eng.prefix_cache.by_key) \
            == ref.allocator.free_pages + len(ref.prefix_cache.by_key)


@pytest.mark.parametrize('mode, kw', [
    ('pipelined', {}),
    ('unpipelined', {'pipeline_decode': False}),
    ('speculative', {'speculative_k': 2}),
    ('decode_chunk', {'decode_chunk': 2}),
])
def test_first_token_counters_follow_the_loop(llama_tiny, mode, kw):
    """Only the plain pipelined loop defers; the loops that read the
    token on the host keep the blocking fetch. /stats serves both
    counts and the phase keeps its name and its count of passes."""
    model, params = llama_tiny
    requests = HANDOFF_REQUESTS[:3]
    rows, eng = _hand_driven(model, params, requests, **kw)
    greedy, _ = _hand_driven(model, params, requests,
                             pipeline_decode=False)
    assert rows == greedy
    n = len(requests)
    assert (eng.first_tokens_deferred, eng.first_tokens_synced) == \
        ((n, 0) if mode == 'pipelined' else (0, n))
    # A phase a pass, not a prompt: the second prompt's 3-token tail
    # and the third's 4 tokens share one pass of the 8-token budget.
    assert eng.phases.n('engine.first_token_sync') == 2


def _device_to_host_spy(monkeypatch, log):
    """Log every fetch of a device array's value: `jax.device_get`,
    `np.asarray`, `int()`."""
    from jax._src import array
    value = array.ArrayImpl._value  # pylint: disable=protected-access

    def spy(self):
        log.append('device_get')
        return value.fget(self)

    monkeypatch.setattr(array.ArrayImpl, '_value', property(spy))


@pytest.mark.parametrize('deferred', [True, False])
def test_no_fetch_between_last_chunk_and_next_round(
        llama_tiny, monkeypatch, deferred):
    """The pass that finishes a prompt: the chunk's dispatch is
    followed by the next round's dispatch with no fetch from the
    device in between, and the round in flight is fetched after both.
    The unpipelined loop, as the control, does fetch in between."""
    model, params = llama_tiny
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=64, prefill_chunk=8,
                                   pipeline_decode=deferred)
    eng.stop()
    running = eng.submit([5, 9, 2, 17], max_new_tokens=12)
    eng._admit()
    eng._prefill_work()
    eng._decode_step()
    eng._decode_step()              # `running` has a round in flight
    joining = eng.submit(list(range(2, 14)), max_new_tokens=3)
    eng._admit()
    eng._prefill_work()             # first of two chunks
    eng._decode_step()
    assert eng.prefilling[1] and (eng._inflight is not None) == deferred

    log = []
    run_chunk, decode = eng._run_prefill_chunk, eng._decode
    monkeypatch.setattr(
        eng, '_run_prefill_chunk',
        lambda *a: (log.append('chunk'), run_chunk(*a))[1])
    monkeypatch.setattr(
        eng, '_decode',
        lambda *a, **k: (log.append('decode'), decode(*a, **k))[1])
    _device_to_host_spy(monkeypatch, log)
    eng._prefill_work()             # the prompt's last chunk
    assert eng.active[1] and not eng.prefilling[1]
    eng._decode_step()
    monkeypatch.undo()
    if deferred:
        assert log[:2] == ['chunk', 'decode'] and \
            'device_get' in log[2:]
        # The round went out with a token the host has not seen.
        assert eng._inflight['joined'][1] and eng.cur_token[1] == 0
    else:
        assert log[:3] == ['chunk', 'device_get', 'decode']
    while eng.active.any() or eng._inflight is not None:
        eng._decode_step()
    assert len(running.result(timeout=5)) == 4 + 12
    assert len(joining.result(timeout=5)) == 12 + 3


def test_handoff_compiles_nothing_for_another_count_of_prompts(
        llama_tiny):
    """The handoff's programs have the engine's shapes, not the
    pass's: a pass that finishes one prompt compiles them, and passes
    that finish three at once (under a larger budget), or a prompt in
    another slot, lower nothing new."""
    import jax.monitoring
    model, params = llama_tiny
    lowered = []

    def listener(event, duration, **kw):
        del duration, kw
        if event == '/jax/core/compile/jaxpr_to_mlir_module_duration':
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        _, eng = _hand_driven(model, params, [([5, 9, 2, 17], 3)],
                              num_slots=4, prefill_budget=32)
        assert eng.phases.n('engine.first_token_sync') == 1
        n0 = len(lowered)
        assert n0 > 0
        three = [([3, 3, 3, 3], 3), ([7, 8, 9, 10], 4),
                 ([11, 12, 13, 14], 2)]
        _serve_by_hand(eng, three)
        assert eng.first_tokens_deferred == 4 and \
            eng.phases.n('engine.first_token_sync') == 2
        assert len(lowered) == n0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize('how', ['cancel', 'preempt', 'evacuate',
                                 'deadline'])
def test_lane_torn_down_between_handoff_and_first_commit(
        llama_tiny, how):
    """A lane that leaves after its first token was handed to a round
    and before that round's commit: its pages go back, nobody streams
    its token, and the other lane's tokens are what they would have
    been alone."""
    from skypilot_tpu.robustness.errors import (DeadlineExceededError,
                                                SessionMigratedError)
    model, params = llama_tiny
    keeper = ([5, 9, 2, 17], 10)
    alone, ref = _hand_driven(model, params, [keeper], num_slots=2)

    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   max_total_len=64, prefill_chunk=8)
    eng.stop()
    kept = eng.submit(keeper[0], max_new_tokens=keeper[1])
    eng._admit()
    eng._prefill_work()
    eng._decode_step()
    streamed = []
    victim_prompt = list(range(20, 30))
    victim = eng.submit(victim_prompt, max_new_tokens=6,
                        on_token=streamed.append)
    eng._admit()
    eng._prefill_work()
    eng._decode_step()
    eng._prefill_work()             # last chunk: handoff
    eng._decode_step()              # the round that carries the token
    assert eng.active[1] and eng._inflight['joined'][1]
    assert eng.first_tokens_deferred == 2 and not streamed

    if how == 'cancel':
        eng.cancel([victim])
        eng._apply_cancellations()
        assert victim.result(timeout=5) == victim_prompt
    elif how == 'deadline':
        eng.deadlines[1] = 1e-9
        eng._reap_deadlines()
        with pytest.raises(DeadlineExceededError):
            victim.result(timeout=5)
    elif how == 'evacuate':
        record = eng._evacuate_slot(1, 'drain')
        assert record['tokens'] == victim_prompt    # committed only
        with pytest.raises(SessionMigratedError):
            victim.result(timeout=5)
    else:
        # Page pressure at the next dispatch: the pool has nothing
        # left and the victim's next position needs a page.
        hoard = eng.allocator.allocate(eng.allocator.free_pages)
        eng.prefix_cache = None
        eng.allocated_tokens[1] = eng.pos[1] + 1
        eng._decode_step()
        assert eng.preemptions == 1 and not eng.active[1]
        assert not victim.done() and len(eng._ready) == 1
        eng.allocator.release(hoard)
        eng._ready.clear()
    assert not eng.active[1] and eng.futures[1] is None
    while eng.active.any() or eng._inflight is not None:
        eng._decode_step()
    assert kept.result(timeout=5) == alone[0]
    assert not streamed
    if how != 'preempt':
        assert eng.allocator.free_pages + len(eng.prefix_cache.by_key) \
            == ref.allocator.free_pages + len(ref.prefix_cache.by_key)
