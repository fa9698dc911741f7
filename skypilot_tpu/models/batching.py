"""Continuous batching: a slot-based decode engine for LM serving.

JetStream-shaped, TPU-first: all device work is fixed-shape jitted
functions. A fixed pool of `num_slots` decode slots shares one KV
cache; requests prefill into a free slot (prompt lengths bucketed to
limit recompiles) and then ride the shared decode loop, leaving as
they finish — new requests join WITHOUT waiting for the batch to
drain, which is what lifts serving throughput under ragged request
lengths (the reference orchestrates external engines with this
property; here the engine is in-framework, over models/llama.py's
per-row-position KV cache). With `speculative_k > 0` the loop runs
prompt-lookup verify chunks instead of single tokens: every slot
(greedy and sampled, paged and dense) commits 1..K+1 tokens per model
call, exactly preserving the non-speculative output distribution.

Two stall-free-scheduler mechanisms (Sarathi/vLLM split-fuse style):

  - CHUNKED PREFILL (`prefill_chunk=C`): an admitted prompt's suffix
    prefills in fixed C-token chunks (one compiled shape, plus small
    power-of-two tails) under a per-iteration token budget, with
    decode steps interleaved between chunks — one 4k-token prompt no
    longer stalls every active decode slot for a whole forward pass,
    and padding waste is bounded by the chunk, not a log2 bucket.
  - PIPELINED DECODE (`pipeline_decode`): decode round N+1 is
    dispatched (JAX async dispatch) BEFORE round N's tokens are
    fetched and committed, so host-side stop-detection/streaming
    overlaps device compute and the accelerator's dispatch queue
    stays non-empty. Greedy outputs are token-for-token identical to
    the unpipelined loop; lanes that finish mid-pipeline leave one
    junk write past their last committed position (the same
    write-before-read contract speculation relies on). A prompt that
    finishes prefilling joins the same way: its first token is
    sampled on the device, fed to the next round from there and
    fetched with that round's tokens, so the scheduler never waits
    for a chunk.

Use via `ContinuousBatchingEngine.submit(prompt) -> Future`, or the
HTTP server in recipes/serve_lm.py (--continuous-batching).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import lora as lora_lib
from skypilot_tpu.models.generate import sample_tokens
from skypilot_tpu.observability import catalog as _obs
from skypilot_tpu.observability import flight as flight_lib
from skypilot_tpu.observability import tracing
from skypilot_tpu.robustness import faults
from skypilot_tpu.robustness.errors import (AdapterNotFoundError,
                                            DeadlineExceededError,
                                            EngineDeadError,
                                            QueueSaturatedError,
                                            SessionMigratedError)


def _bucket(n: int, cap: int) -> int:
    """Next power of two >= n (bounded): limits prefill recompiles."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


# The two small programs of the first-token handoff. Every operand has
# the engine's [num_slots] (or scalar) shape and the slot is traced,
# so neither compiles anew for another slot or another count of
# prompts finished in a pass.
@jax.jit
def _stash_first_token(first_tokens, slot, token):
    """`first_tokens` with `token` at `slot`; not donated: a round in
    flight keeps the vector it was dispatched with."""
    return first_tokens.at[slot].set(token.astype(jnp.int32))


@jax.jit
def _merge_cur_tokens(cont, sampled, joined, first_tokens, cur_token):
    """A round's input tokens from their three sources: the round in
    flight for continuing lanes, the handoff vector for lanes that
    joined since, the host's `cur_token` for the rest."""
    return jnp.where(cont, sampled,
                     jnp.where(joined, first_tokens, cur_token))


class PrefixCache:
    """Content-addressed KV page reuse across requests (the vLLM
    automatic-prefix-caching idea, TPU-paged form).

    Every FULL page of a prompt gets a chain key (hash of all tokens
    up to and including that page), so two requests sharing a system
    prompt map their common full pages to the SAME physical pages —
    admission skips recomputing them (prefill runs only the suffix)
    and the pool holds one copy. Pages of finished prompts stay
    RESIDENT but unreferenced (LRU), evicted back to the allocator
    only under pool pressure. Shared pages are never written: suffix
    prefill and decode both write at positions past the cached
    region, and the masked tail of a padded chunk lands in the trash
    page (the paged-KV contract, docs/internals.md §4).
    """

    def __init__(self, page_size: int,
                 metrics: Optional['_obs.EngineMetrics'] = None,
                 spill=None, fetch_pages=None, flight=None) -> None:
        self.page_size = page_size
        self.by_key: Dict[bytes, int] = {}
        self.key_of: Dict[int, bytes] = {}
        self.refs: Dict[int, int] = {}
        # Resident-but-unreferenced pages, oldest first (evictable).
        self.lru: 'collections.OrderedDict[int, None]' = \
            collections.OrderedDict()
        self.hits = 0       # pages served from cache
        self.misses = 0     # full prompt pages that had to be computed
        self.evictions = 0  # cached pages returned under pool pressure
        self._metrics = metrics  # owning engine's Prometheus bundle
        # Tiered cache (inference/kv_transfer.HostSpillTier): evicted
        # pages spill — exact device bytes, fetched by the engine's
        # `fetch_pages(pages) -> {leaf_path: page-major array}` — and
        # are restored on a later chain-key hit instead of recomputed.
        # None keeps the classic drop-on-evict behavior.
        self.spill = spill
        self._fetch_pages = fetch_pages
        self.spilled_pages = 0
        # Owning engine's flight recorder (observability/flight.py):
        # evict/spill decisions land in its ring. None = standalone.
        self._flight = flight

    @staticmethod
    def chain_keys(tokens, page_size: int,
                   salt: bytes = b'') -> List[bytes]:
        """One key per FULL page; key_i commits to ALL tokens through
        page i, so equal keys imply equal attention history. `salt`
        prefixes the chain (the adapter identity): once LoRA touches
        the k/v projections, a page's contents depend on WHICH
        adapter computed it — un-salted keys would serve one tenant's
        KV pages to another (inference/affinity.py re-derives the
        same salted keys for LB routing)."""
        import hashlib
        keys = []
        h = hashlib.sha256()
        if salt:
            h.update(salt)
        for i in range(len(tokens) // page_size):
            chunk = tokens[i * page_size:(i + 1) * page_size]
            h.update(np.asarray(chunk, np.int32).tobytes())
            keys.append(h.digest())
        return keys

    def lookup_acquire(self, keys: List[bytes],
                       record: bool = True) -> List[int]:
        """Longest cached prefix of `keys`; takes a reference on each
        returned page (pinned against eviction). `record=False`
        defers the hit/miss accounting to the caller (the engine's
        spill-restore path extends the prefix first, then records the
        post-restore truth — a restored page avoided the recompute
        exactly like a resident hit)."""
        pages = []
        for key in keys:
            page = self.by_key.get(key)
            if page is None:
                break
            pages.append(page)
            self.refs[page] = self.refs.get(page, 0) + 1
            self.lru.pop(page, None)
        if record:
            self.record_lookup(len(pages), len(keys) - len(pages))
        return pages

    def record_lookup(self, n_hits: int, n_misses: int) -> None:
        self.hits += n_hits
        self.misses += n_misses
        if self._metrics is not None:
            self._metrics.prefix_hits.inc(n_hits)
            self._metrics.prefix_misses.inc(n_misses)

    def acquire_page(self, key: bytes, page: int) -> None:
        """Adopt + immediately reference a page the engine just
        restored/imported into the pool under `key` (the
        insert-then-acquire composition, minus the LRU round trip)."""
        if not self.insert(key, page):
            raise ValueError(f'key already cached: {key.hex()[:12]}')
        self.lru.pop(page, None)
        self.refs[page] = self.refs.get(page, 0) + 1

    def release(self, pages: List[int]) -> None:
        for page in pages:
            self.refs[page] -= 1
            if self.refs[page] == 0:
                del self.refs[page]
                self.lru[page] = None  # newest evictable

    def insert(self, key: bytes, page: int) -> bool:
        """Adopt ownership of `page` under `key`; False = key already
        cached (caller keeps the page and releases it normally)."""
        if key in self.by_key:
            return False
        self.by_key[key] = page
        self.key_of[page] = key
        self.lru[page] = None
        return True

    def evict_into(self, allocator, need: int) -> None:
        """Return unreferenced cached pages to the allocator until it
        can serve `need` pages (or the evictable set is dry). With a
        spill tier the victims' device bytes are fetched in ONE
        batched gather and spilled (payload + scales + chain key)
        before their pages are released — restore on a later hit is
        bit-identical to the fresh compute."""
        deficit = need - allocator.free_pages
        if deficit <= 0:
            return
        victims: List[tuple] = []
        while len(victims) < deficit and self.lru:
            page, _ = self.lru.popitem(last=False)
            key = self.key_of.pop(page)
            del self.by_key[key]
            victims.append((key, page))
        if not victims:
            return
        if self.spill is not None and self._fetch_pages is not None:
            from skypilot_tpu.inference import kv_transfer
            try:
                blobs = self._fetch_pages([p for _, p in victims])
                per_page = kv_transfer.split_pages(blobs, len(victims))
                for (key, _page), blob in zip(victims, per_page):
                    self.spill.put(key, blob)
                    self.spilled_pages += 1
                    if self._metrics is not None:
                        self._metrics.kv_spill_pages.inc()
                if self._flight is not None:
                    self._flight.record('spill', pages=len(per_page))
            except Exception as e:  # pylint: disable=broad-except
                # Spilling is an optimization: a failed gather must
                # degrade to the classic drop-on-evict, never block
                # the admission that triggered the eviction.
                print(f'prefix cache: spill of {len(victims)} pages '
                      f'failed ({type(e).__name__}: {e}); dropping '
                      f'them instead', flush=True)
        for _, page in victims:
            allocator.release([page])
            self.evictions += 1
            if self._metrics is not None:
                self._metrics.prefix_evictions.inc()
        if self._flight is not None:
            self._flight.record('evict', pages=len(victims))


class ContinuousBatchingEngine:

    # Prometheus `engine` label values: one per engine instance in
    # this process (the serving runtime may run two — the main engine
    # plus the lazy stream engine).
    _instance_ids = itertools.count()

    # Thread-ownership contract, machine-checked by SKY008 (see
    # analysis/callgraph.py for the grammar and docs/internals.md
    # "Thread-ownership model"). Everything below is touched only by
    # the scheduler thread (_loop); cross-thread work hops through
    # run_on_scheduler. `cache` is STRICT ('scheduler!'): every
    # dispatch DONATES it, so even a read from another thread races
    # the dispatch that consumes the buffer. The scrape/HTTP threads'
    # racy snapshot reads of the non-strict counters and slot arrays
    # are deliberate (stale-but-consistent-enough stats) — reads of
    # non-strict attrs are allowed; writes are not.
    _STPU_OWNERS = {
        'cache': 'scheduler!',
        # slot arrays + per-slot bookkeeping
        'cur_token': 'scheduler', 'pos': 'scheduler',
        'active': 'scheduler', 'prefilling': 'scheduler',
        'prefill_frontier': 'scheduler', 'prompt_len': 'scheduler',
        'outputs': 'scheduler', 'limits': 'scheduler',
        'temps': 'scheduler', 'top_ks': 'scheduler',
        'top_ps': 'scheduler', 'stop_ids': 'scheduler',
        'on_tokens': 'scheduler', 'deadlines': 'scheduler',
        'slot_adapter': 'scheduler', 'slot_adapter_name': 'scheduler',
        '_prefill_order': 'scheduler', '_prefill_t0': 'scheduler',
        '_slot_ctx': 'scheduler',
        # paged-KV state (rebuilt by _reset_paging on the scheduler)
        'allocator': 'scheduler', 'page_table': 'scheduler',
        'owned_pages': 'scheduler', 'allocated_tokens': 'scheduler',
        'prefix_cache': 'scheduler', 'shared_pages': 'scheduler',
        'slot_keys': 'scheduler',
        # dispatch plumbing
        '_rng': 'scheduler', '_inflight': 'scheduler',
        '_first_tokens': 'scheduler', '_first_pending': 'scheduler',
        '_prefill_fns': 'scheduler', '_scatter_fns': 'scheduler',
        '_cache_shardings': 'scheduler',
        # pipeline-stage dispatch state (PR 19): the per-group
        # in-flight ring, the per-stage jitted-fn cache, and the last
        # prefill pass's schedule bubble (scrape threads read the
        # float racily, like the counters).
        '_group_inflight': 'scheduler', '_stage_fns': 'scheduler',
        '_prefill_bubble': 'scheduler',
        # counters (scrape threads read these racily, on purpose)
        'decode_calls': 'scheduler', 'tokens_committed': 'scheduler',
        'preemptions': 'scheduler', 'prefill_chunks_run': 'scheduler',
        'first_tokens_deferred': 'scheduler',
        'first_tokens_synced': 'scheduler',
        'phases': 'scheduler',
        'last_prefill_tokens': 'scheduler',
        'kv_restored_pages': 'scheduler',
        'kv_restore_lookups': 'scheduler',
        'kv_restore_hits': 'scheduler',
        'deadline_exceeded': 'scheduler', 'engine_restarts': 'scheduler',
        '_soft_errors': 'scheduler', 'soft_errors_total': 'scheduler',
        # live-migration counters (PR 20): evacuated sessions and the
        # subset that shipped a packed KV chain with them
        'sessions_evacuated': 'scheduler',
        'chains_evacuated': 'scheduler',
    }

    def __init__(self, model, params, *, num_slots: int = 8,
                 max_total_len: int = 256, temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 paged: Optional[bool] = None,
                 prefix_caching: bool = True,
                 speculative_k: int = 0, spec_ngram: int = 2,
                 spec_lookback: int = 512,
                 decode_chunk: int = 1,
                 prefill_chunk: int = 0,
                 prefill_budget: int = 0,
                 pipeline_decode: Optional[bool] = None,
                 max_queue_requests: int = 0,
                 max_queue_tokens: int = 0,
                 adapter_store=None,
                 kv_spill_bytes: int = 0,
                 kv_cold_dir: Optional[str] = None,
                 mesh=None) -> None:
        assert max_total_len <= model.config.max_seq_len
        # Mesh-sharded device state (parallel/serving.py): with a
        # mesh, the KV cache is EXPLICITLY placed — paged pool values
        # shard their kv-heads axis over `tensor` (GQA remainder
        # rule: replicate when heads don't divide), scale pages
        # replicate — and every jitted dispatch pins the donated
        # cache's out_sharding, so an N-chip mesh holds ~N x the
        # pages at fixed per-chip HBM with zero per-step resharding.
        self.mesh = mesh
        self.mesh_devices = (int(mesh.devices.size)
                             if mesh is not None else 1)
        self._cache_shardings = None
        # Pipeline stages (PR 19): a (stage, tensor) mesh splits the
        # model's layers into contiguous per-stage ranges; each stage
        # is a tensor-parallel submesh with its OWN params, cache and
        # jitted dispatches, chained host-side per round. 1 = the
        # classic single-program engine (tensor-only or one device).
        self.stages = (int(mesh.shape.get('stage', 1))
                       if mesh is not None else 1)
        # Multi-LoRA serving (inference/adapters.py): each slot may
        # carry an adapter id into the shared dispatch; the model
        # gathers per-slot A/B factors from the store's stacked
        # tensors. None = base-model-only engine (no LoRA code runs).
        if adapter_store is not None and not lora_lib.supports(model):
            raise ValueError(
                f'{type(model).__name__} has no LoRA forward path; '
                f'serve adapters with a Llama-family model or drop '
                f'--adapter-dir')
        self.adapter_store = adapter_store
        # Chunked decode: N single-token steps in ONE jitted lax.scan
        # dispatch (the serving analog of the trainer's multi-step) —
        # outputs are BIT-IDENTICAL to step-by-step because the rng
        # split chain is the same, and post-limit/post-eos junk writes
        # follow the speculative write-before-read contract. Pays
        # where per-dispatch host overhead dominates the decode step
        # (on the chip: not measured, ROADMAP S3a); costs up to N-1
        # wasted steps per finishing request and batches admission at
        # chunk boundaries. Mutually exclusive with speculation (verify
        # chunks already amortize dispatches).
        assert decode_chunk >= 1
        assert not (decode_chunk > 1 and speculative_k), (
            'decode_chunk composes with the plain decode loop only; '
            'speculative verify chunks already commit multiple tokens '
            'per dispatch')
        self.decode_chunk = decode_chunk
        if decode_chunk > 1:
            assert max_total_len + decode_chunk <= \
                model.config.max_seq_len, (
                    f'decode_chunk={decode_chunk} writes up to that '
                    f'many positions past a finishing request: '
                    f'max_total_len({max_total_len}) + chunk must be '
                    f'<= max_seq_len({model.config.max_seq_len})')
        if speculative_k:
            # Verification chunks write up to K past the last kept
            # token — same headroom contract as the one-shot
            # speculative engine (models/generate.py).
            assert max_total_len + speculative_k <= \
                model.config.max_seq_len, (
                    f'speculative_k={speculative_k} needs headroom: '
                    f'max_total_len({max_total_len}) + K must be <= '
                    f'max_seq_len({model.config.max_seq_len})')
        # Chunked prefill: the admitted prompt's suffix runs in
        # fixed-size chunks under a per-iteration token budget, with
        # decode steps interleaved — instead of one whole-prompt
        # forward pass that stalls every active decode slot.
        # prefill_chunk=0 keeps the single-shot path (whole suffix in
        # one log2-bucketed dispatch, budget unbounded).
        if prefill_chunk < 0:
            raise ValueError(
                f'prefill_chunk must be >= 0, got {prefill_chunk}')
        self.prefill_chunk = prefill_chunk
        if prefill_chunk and 0 < prefill_budget < prefill_chunk:
            raise ValueError(
                f'prefill_budget={prefill_budget} < prefill_chunk='
                f'{prefill_chunk}: the budget is spent in whole '
                f'chunks, so no chunk could ever be issued')
        # Effective tokens-per-iteration cap; default = one chunk per
        # loop iteration (maximal decode interleaving).
        self.prefill_budget = ((prefill_budget or prefill_chunk)
                               if prefill_chunk else 0)
        # One-step host/device pipelining: dispatch decode round N+1
        # before committing round N, so stop-detection/streaming
        # overlaps device compute. Composes with the PLAIN decode loop
        # only — verify chunks and decode chunks already amortize
        # dispatches and fetch multi-token results the host must
        # reconcile synchronously. Auto mode (None) enables it exactly
        # when the plain loop runs.
        if pipeline_decode and (speculative_k or decode_chunk > 1):
            raise ValueError(
                'pipeline_decode composes with the plain decode loop '
                'only; speculative_k and decode_chunk dispatch '
                'multi-token rounds that are committed synchronously '
                '(set pipeline_decode=None/False with those modes)')
        self.pipeline_decode = (not speculative_k and decode_chunk == 1
                                if pipeline_decode is None
                                else bool(pipeline_decode))
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_total_len = max_total_len
        self.temperature = temperature
        self.eos_id = eos_id
        self.spec_k = speculative_k
        self.spec_ngram = spec_ngram
        self.spec_lookback = spec_lookback

        # Paged KV cache (vLLM-style; ops/paged_attention.py): K/V live
        # in a shared physical page pool sized for the AGGREGATE live
        # tokens instead of num_slots * max_total_len, with host-side
        # incremental page allocation. Auto-on for models that declare
        # kv_page_size/kv_total_pages (llama/gpt/mixtral) when the
        # pool can hold a full-depth sequence.
        # What a cached token's row is made of comes from the model
        # (ops/paged_attention.PageLayout): K and V heads, or MLA's
        # latent row and indexer key. None: the model has no pool.
        self.page_layout = (model.config.page_layout()
                            if hasattr(model.config, 'page_layout')
                            else None)
        cfg_page = self.page_layout.page_size if self.page_layout else 0
        cfg_pool = self.page_layout.total_pages if self.page_layout else 0
        # Speculative verify chunks write K tokens — and decode chunks
        # N-1 tokens — past the last committed one: the pool and each
        # row's page table carry that headroom.
        self._write_lookahead = max(self.spec_k, self.decode_chunk - 1)
        pool_ok = (cfg_page > 0 and cfg_pool > 0 and
                   (cfg_pool - 1) * cfg_page >=
                   max_total_len + self._write_lookahead)
        if paged is None:
            # Auto-on only when the pool can hold at least ONE
            # full-depth sequence — a small default pool must not
            # silently cap servable lengths below max_total_len (the
            # dense path has no such cap).
            paged = pool_ok
        elif paged and not pool_ok:
            raise ValueError(
                f'paged=True but kv_total_pages={cfg_pool} x '
                f'kv_page_size={cfg_page} cannot hold one '
                f'max_total_len={max_total_len} sequence '
                f'(+{self._write_lookahead} chunk-write headroom; '
                f'usable {(max(cfg_pool - 1, 0)) * cfg_page} tokens; '
                f'page 0 is reserved).')
        self.paged = paged
        # Which cache layout this engine runs, and why — said out
        # loud (and in /stats `kv_cache`): a default pool too small
        # for one sequence used to select the dense cache silently.
        usable = max(cfg_pool - 1, 0) * cfg_page
        if paged:
            self.kv_cache_choice = (
                f'paged: pool of {cfg_pool} pages x {cfg_page} tokens '
                f'holds max_total_len={max_total_len} '
                f'(+{self._write_lookahead} write headroom)')
        elif cfg_page > 0 and cfg_pool > 0:
            self.kv_cache_choice = (
                f'dense: the page pool ({cfg_pool} pages x {cfg_page} '
                f'tokens, {usable} usable) cannot hold one '
                f'max_total_len={max_total_len} sequence '
                f'(+{self._write_lookahead} write headroom); size it '
                f'with serve_lm --kv-pool-bytes to get the paged pool')
        else:
            self.kv_cache_choice = (
                f'dense: {type(model.config).__name__} declares no '
                f'kv_page_size/kv_total_pages')
        # KV storage format (models/llama.py LlamaConfig.kv_dtype):
        # int8 pages + parallel scale arrays. Quantization lives
        # entirely inside the model's cache variables and the
        # paged-attention ops — the scheduler's page bookkeeping
        # (alloc/free/prefix sharing/chain keys) is format-blind.
        self.kv_dtype = getattr(model.config, 'kv_dtype', 'bf16')
        if self.kv_dtype not in ('bf16', 'int8'):
            raise ValueError(
                f'unsupported kv_dtype {self.kv_dtype!r} '
                f"(choices: 'bf16', 'int8')")
        if self.kv_dtype == 'int8' and not self.paged:
            raise ValueError(
                'kv_dtype=int8 requires the paged KV cache: the '
                'dense per-slot cache has no scale storage (size the '
                'kv page pool to hold max_total_len, or serve bf16)')
        # State BY SLOT beside the pages (a model with state-space
        # layers: ops/paged_attention.SlotArray): a sequence is then
        # more than its pages, and what assumes otherwise is refused
        # by name or turned off here.
        self.slot_state = bool(self.page_layout is not None
                               and self.page_layout.slot_arrays)
        if self.slot_state:
            self._refuse_slot_state({
                'the dense per-slot cache (size the page pool: '
                '--kv-pool-bytes)': not self.paged,
                'an int8 pool (--kv-dtype int8)': self.kv_dtype == 'int8',
                'pipeline stages (--stages)': self.stages > 1,
                'a tensor mesh (--tensor)': self.mesh_devices > 1,
                'speculative decoding (--speculative)': bool(speculative_k),
                'decode chunks (--decode-chunk)': decode_chunk > 1,
                'the spill tier (--kv-spill-bytes, --kv-cold-dir)':
                    bool(kv_spill_bytes or kv_cold_dir),
            })
            if prefix_caching:
                # A cached prefix is pages; the state after it is not
                # in them, so a hit could not be resumed from.
                print(f'engine: {type(model.config).__name__} keeps '
                      f'recurrent state by slot: prefix caching is off '
                      f'(a shared page holds no state to resume from; '
                      f'ROADMAP R-M5)', flush=True)
                prefix_caching = False
        if self.paged and self.page_layout.kind != 'kv':
            # What cannot take another layout than K/V yet refuses
            # here, by name, and not silently (ROADMAP R-M1, D3a).
            asked = {
                'an int8 pool (--kv-dtype int8)': self.kv_dtype == 'int8',
                'pipeline stages (--stages)': self.stages > 1,
                'speculative decoding (--speculative)': bool(speculative_k),
                'decode chunks (--decode-chunk)': decode_chunk > 1,
                'the spill tier (--kv-spill-bytes, --kv-cold-dir)':
                    bool(kv_spill_bytes or kv_cold_dir),
                'LoRA adapters (--adapter-dir)': adapter_store is not None,
            }
            refused = [name for name, on in asked.items() if on]
            if refused:
                raise ValueError(
                    f'a {self.page_layout.kind!r} page layout '
                    f'({type(model.config).__name__}) does not serve '
                    f'with {", ".join(refused)} yet')
        if self.paged:
            # A prompt's first chunk starts at 0. A later one starts a
            # whole number of pages (the prefix hit) and of chunks in:
            # on a page boundary iff chunks are whole pages. That is
            # the promise the suffix dispatches pass to write_kv_chunk
            # (page_aligned), which then writes pages, not tokens.
            self._suffix_page_aligned = prefill_chunk % cfg_page == 0
            self.page_size = cfg_page
            self.total_pages = cfg_pool
            self.pages_per_seq = -(
                -(max_total_len + self._write_lookahead)
                // self.page_size)
        # Ways the KV-heads axis actually shards (1 = replicated
        # pool — single device, or the GQA remainder rule fired).
        # Surfaced in /stats `page_pool.shard_ways` so operators can
        # see whether the mesh is buying pool capacity.
        self.kv_shard_ways = 1
        if mesh is not None:
            from skypilot_tpu.parallel import serving as _tp_serving
            self.kv_shard_ways = _tp_serving.kv_shard_ways(
                int(getattr(model.config, 'num_kv_heads', 0) or 0),
                int(mesh.shape.get('tensor', 1)))
        # Staged build: split the param tree by stage and place each
        # stage on its tensor submesh (parallel/serving.py
        # build_staged_serving). From here on `self.params` and
        # `self.cache` are LISTS of per-stage trees — a list of
        # pytrees is itself a pytree, so the tree-walking helpers
        # (kv_cache_bytes, _cache_lost, weight accounting) apply
        # unchanged.
        self._stage_models: List[Any] = []
        self._stage_submeshes: List[Any] = []
        self._stage_ranges: List[Any] = []
        self._stage_replicated: List[Any] = []
        self._stage_fns: Dict[Any, Any] = {}
        if self.stages > 1:
            if not self.paged:
                raise ValueError(
                    'stages > 1 requires the paged KV cache: the '
                    'per-stage pool split is a split of the page '
                    'pool (declare kv_page_size/kv_total_pages)')
            if self.decode_chunk > 1:
                raise ValueError(
                    'decode_chunk > 1 does not compose with stages: '
                    'the chunk lax.scan would cross submeshes inside '
                    'one jit (use pipeline_decode, the staged engine '
                    'overlaps rounds across stages instead)')
            if self.num_slots % self.stages:
                raise ValueError(
                    f'num_slots={self.num_slots} must divide evenly '
                    f'into stages={self.stages} slot groups (the '
                    f'S-deep decode ring partitions slots per stage)')
            from skypilot_tpu.inference import quant as quant_lib
            if isinstance(model, quant_lib.QuantizedModel) or \
                    quant_lib.is_quantized(params):
                raise ValueError(
                    'int8 WEIGHTS do not compose with stages yet '
                    '(int8 KV pages do): serve quantized weights '
                    'tensor-only, or bf16 weights staged')
            from jax.sharding import NamedSharding, PartitionSpec
            (self._stage_models, params, self._stage_submeshes,
             self._stage_ranges) = _tp_serving.build_staged_serving(
                 model, params, mesh)
            self._stage_replicated = [
                NamedSharding(sub, PartitionSpec())
                for sub in self._stage_submeshes]
            self.params = params
        self.prefix_caching = bool(prefix_caching and self.paged)
        self.prefix_cache: Optional[PrefixCache] = None  # set per reset
        # Tiered prefix cache: evicted pages spill to a bounded
        # host-RAM LRU (optionally backed by a cold directory / gs://
        # prefix) and restore bit-identically on a chain-key hit.
        # The tier OUTLIVES engine resets (content-addressed host
        # bytes stay valid across a crash-only cache rebuild).
        if (kv_spill_bytes or kv_cold_dir) and not self.prefix_caching:
            raise ValueError(
                'kv_spill_bytes/kv_cold_dir need the paged engine '
                'with prefix caching enabled (the spill tier stores '
                'evicted prefix-cache pages)')
        from skypilot_tpu.inference import kv_transfer as _kvt
        self.spill_tier = _kvt.make_spill_tier(kv_spill_bytes,
                                               kv_cold_dir)
        # Restore accounting (the spill tier's own stats count host
        # lookups; these count the engine-level outcome).
        self.kv_restored_pages = 0
        self.kv_restore_lookups = 0
        self.kv_restore_hits = 0

        # Prometheus instruments (observability/catalog.py), labeled
        # by engine instance; counters tick at the event sites below,
        # gauges refresh in update_metric_gauges() at scrape time.
        self.engine_id = str(next(self._instance_ids))
        print(f'engine {self.engine_id}: KV cache = '
              f'{self.kv_cache_choice}', flush=True)
        self.metrics = _obs.EngineMetrics(self.engine_id)
        self.metrics.num_slots.set(num_slots)
        self._weight_bytes: Optional[int] = None  # lazy (roofline)
        # Flight recorder (observability/flight.py): every scheduler
        # decision lands in this bounded ring, unconditionally —
        # served at /debug/flight and snapshotted to a file on
        # reset/death. Single-writer (the scheduler thread);
        # deliberately lock-free, so SKY003 does not apply to it.
        self.flight = flight_lib.FlightRecorder(
            name=f'engine{self.engine_id}')

        # _fresh_cache is the single paging-reset point (also the
        # error-recovery path).
        self.cache = self._fresh_cache()
        # A K pool's static shape, for `attention_impl()`: the decode
        # read's route hangs on it, and scrape threads may not read
        # the scheduler's `self.cache`. None without a paged pool.
        self._pool_aval = next(
            (jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
             for leaf in jax.tree.leaves(self.cache)
             if leaf.shape == self.page_layout.shape(
                 self.page_layout.arrays[0])),
            None) if self.paged else None
        # A model whose layers tell live tokens from the junk lanes
        # that ride every round (routed experts, which would read an
        # expert's weights for a junk token; its device counters) is
        # handed the mask: `live` in its call.
        self._takes_live = bool(getattr(model, 'takes_live_mask', False))

        # Host-side slot bookkeeping (device work stays fixed-shape).
        # A slot is OCCUPIED when `prefilling` (admitted, prompt
        # suffix still being written into the cache chunk by chunk)
        # or `active` (prefilled, riding the shared decode loop).
        self.cur_token = np.zeros((num_slots,), np.int32)
        self.pos = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        self.prefilling = np.zeros((num_slots,), bool)
        # Next prompt position the slot's prefill will write. While a
        # slot prefills, `pos` tracks this frontier too, so the decode
        # loop's junk write for the (inactive) lane lands at a
        # position the NEXT chunk overwrites before attending.
        self.prefill_frontier = np.zeros((num_slots,), np.int32)
        self.prompt_len = np.zeros((num_slots,), np.int32)
        self.outputs: List[List[int]] = [[] for _ in range(num_slots)]
        self.futures: List[Optional[Future]] = [None] * num_slots
        self.limits = np.zeros((num_slots,), np.int32)
        self.temps = np.zeros((num_slots,), np.float32)
        self.top_ks = np.zeros((num_slots,), np.int32)   # 0 = off
        self.top_ps = np.ones((num_slots,), np.float32)  # 1 = off
        self.stop_ids: List[frozenset] = [frozenset()] * num_slots
        self.on_tokens: List[Optional[Callable[[int], None]]] = \
            [None] * num_slots
        # Per-slot absolute (monotonic) deadline; 0 = none. The
        # scheduler reaps expired slots between rounds so a
        # deadline-bearing request cannot hold a slot past it.
        self.deadlines = np.zeros((num_slots,), np.float64)
        # Per-slot adapter: device-store row id (0 = base model) and
        # the registry name (for refcount release + token metrics).
        self.slot_adapter = np.zeros((num_slots,), np.int32)
        self.slot_adapter_name: List[Optional[str]] = [None] * num_slots
        # Prefilling slots in admission order: the scheduler finishes
        # the oldest admission's prefill first (FCFS — completing one
        # prompt starts its decode sooner than round-robining all).
        self._prefill_order: 'collections.deque' = collections.deque()
        self._prefill_t0 = [0.0] * num_slots
        # Per-slot distributed-tracing context
        # (observability/tracing.py); None = request not sampled.
        # Scheduler-thread owned, like the other slot arrays.
        self._slot_ctx: List[Optional[Any]] = [None] * num_slots

        # Observability: model calls vs tokens committed (speculation
        # quality = tokens_committed / decode_calls, 1.0..K+1), and
        # page-pressure preemptions (the /stats + /metrics signal that
        # the pool is undersized for the offered load).
        self.decode_calls = 0
        self.tokens_committed = 0
        self.preemptions = 0
        self.prefill_chunks_run = 0
        # Finished prompts by how their first token reached the decode
        # loop: handed to the next round on the device, or fetched in
        # the blocking sync (/stats, /metrics).
        self.first_tokens_deferred = 0
        self.first_tokens_synced = 0
        # The scheduler loop's phases (observability/tracing.phase):
        # self seconds and counts per name, served by /stats
        # (`phases`, `loop_s`, `decode_stall_s`) and /metrics.
        self.phases = tracing.PhaseClock()
        self.last_prefill_tokens = 0     # budget spent, last iteration
        # Live migration (PR 20): sessions evacuated off this engine
        # (drain / preemption notice / rebalance) and the subset whose
        # committed KV chain was packed for shipment.
        self.sessions_evacuated = 0
        self.chains_evacuated = 0

        # Admission control (load shedding): 0 = unbounded. submit()
        # raises QueueSaturatedError instead of queueing past these —
        # a saturated replica answers 429 in microseconds rather than
        # parking requests it will serve after their callers gave up.
        self.max_queue_requests = int(max_queue_requests)
        self.max_queue_tokens = int(max_queue_tokens)
        self._shed_lock = threading.Lock()
        self._queued_tokens_n = 0   # prompt tokens in _queue + _ready
        self.requests_shed = 0
        self.deadline_exceeded = 0
        self.engine_restarts = 0
        self._soft_errors = 0       # consecutive cache-intact errors
        # Every error the scheduler contained, lifetime (/stats
        # `soft_errors`): a kernel the compiler refuses inside a
        # dispatch becomes failed requests on a server that stays up
        # and exits 0, so a health check reads this, not the rc.
        self.soft_errors_total = 0
        # Crash-only: a dead scheduler thread flips this instead of
        # hanging clients (submit fails fast; /readyz reports 503).
        self._dead = threading.Event()

        self._chunk_decode = (self._make_chunk_decode_fn()
                              if self.decode_chunk > 1 else None)
        # Client-abandoned requests (disconnected stream consumers):
        # applied on the scheduler thread between rounds.
        self._cancel_requests: set = set()
        self._cancel_lock = threading.Lock()
        self._queue: 'queue.Queue' = queue.Queue()
        # Control operations (KV chain export/import) hop onto the
        # scheduler thread here: ALL device work — including page
        # gather/scatter — runs between decode rounds on the one
        # thread that owns self.cache (touching a donated buffer from
        # an HTTP thread would race the dispatch that consumes it).
        self._control: 'queue.Queue' = queue.Queue()
        # Jitted page-scatter fns keyed by (padded) chain length.
        self._scatter_fns: Dict[int, Any] = {}
        # FCFS admission order, owned by the scheduler thread: requests
        # drain from _queue into _ready; a stalled (page-pressure) or
        # preempted request returns to the HEAD so later arrivals can't
        # starve it (vLLM-style head-of-line blocking).
        self._ready: 'collections.deque' = collections.deque()
        self._rng = jax.random.PRNGKey(0)
        self._prefill_fns: Dict[Any, Any] = {}
        self._decode = (self._make_spec_decode_fn() if self.spec_k
                        else self._make_decode_fn())
        # Pipelined decode: the dispatched-but-not-committed round
        # (device token array + the host state it was built from).
        self._inflight: Optional[Dict[str, Any]] = None
        # The plain pipelined loop (one program, one token a round:
        # the constructor refuses pipeline_decode beside spec_k or
        # decode_chunk) takes a finished prompt's first token from the
        # device: `_first_tokens[slot]` holds it, `_first_pending`
        # marks the lanes whose token no round has carried yet. The
        # loops that read `cur_token` on the host (speculative drafts,
        # decode chunks, the unpipelined step) or keep a ring of their
        # own (stages) fetch it in `_sync_first_tokens`.
        self._defer_first = self.pipeline_decode and self.stages == 1
        self._first_tokens = jnp.zeros((num_slots,), jnp.int32)
        self._first_pending = np.zeros((num_slots,), bool)
        # Staged decode ring: one in-flight round per slot GROUP
        # (contiguous num_slots/stages slice) — up to S rounds in
        # flight, each occupying a different stage of the chain.
        self._group_inflight: List[Optional[Dict[str, Any]]] = \
            [None] * self.stages
        # Closed-form bubble fraction of the last staged prefill
        # pass's chunk-microbatch schedule ((S-1)/(M+S-1); 0.0 for
        # unstaged engines) — the prefill_bubble_fraction gauge.
        self._prefill_bubble = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(  # stpu: thread[scheduler]
            target=self._loop, daemon=True)
        self._thread.start()

    def _reset_paging(self) -> None:
        from skypilot_tpu.ops import paged_attention as paged_ops
        self.allocator = paged_ops.PageAllocator(self.total_pages,
                                                 self.pages_per_seq)
        # Physical page 0 is the TRASH page: unallocated table entries
        # point at it, so junk writes (inactive slots, padded prefill
        # tails, exhausted slots) can never corrupt a live page.
        trash = self.allocator.allocate(1)
        assert trash == [0], trash
        self.page_table = np.zeros((self.num_slots, self.pages_per_seq),
                                   np.int32)
        self.owned_pages: List[List[int]] = [
            [] for _ in range(self.num_slots)]
        self.allocated_tokens = np.zeros((self.num_slots,), np.int32)
        # Prefix caching (vLLM APC): per-slot shared (read-only) page
        # refs + the prompt's chain keys for promotion on completion.
        # PrefixCache invokes fetch_pages only from restore paths that
        # run on the engine thread, hence the role pin.
        self.prefix_cache = (PrefixCache(
            self.page_size, metrics=self.metrics,
            spill=self.spill_tier,
            fetch_pages=self._gather_page_blobs,  # stpu: role[scheduler]
            flight=self.flight)
            if self.prefix_caching else None)
        self.shared_pages: List[List[int]] = [
            [] for _ in range(self.num_slots)]
        self.slot_keys: List[List[bytes]] = [
            [] for _ in range(self.num_slots)]

    def _fresh_cache(self):
        """Zeroed KV cache for the slot pool. Also the recovery path:
        prefill/decode DONATE the cache buffer, so after a failed
        device execution the old buffer is gone and must be rebuilt."""
        if self.stages > 1:
            return self._fresh_staged_cache()
        kwargs = {}
        if self.paged:
            self._reset_paging()
            kwargs['page_indices'] = jnp.zeros(
                (self.num_slots, self.pages_per_seq), jnp.int32)
        shapes = self._cache_shapes(
            self.model, jnp.zeros((self.num_slots, 1), jnp.int32),
            kwargs)
        if self.mesh is not None and self._cache_shardings is None:
            # Explicit placement: the pool starts on its declared
            # shardings and every dispatch's out_shardings keeps the
            # donated buffer there — the layout survives resets too.
            from skypilot_tpu.parallel import serving as _tp_serving
            self._cache_shardings = \
                _tp_serving.serving_cache_shardings(shapes, self.mesh)
        return self._zeros(shapes, self._cache_shardings)

    def _cache_shapes(self, model, x, kwargs):
        """The model's cache collection as shapes only. `model.init`
        would also run the forward pass and materialize every
        PARAMETER (f32, on the default device) just to be thrown
        away — 32 GB for an 8B model, on one 16 GB chip."""
        import flax.linen as nn
        return nn.meta.unbox(jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), x,
                positions=jnp.zeros((self.num_slots, 1), jnp.int32),
                decode=True, **kwargs)['cache']))

    @staticmethod
    def _zeros(shapes, shardings):
        """Zeroed arrays for `shapes`, created ON their shardings (a
        sharded pool is never whole on one chip, not even at birth)."""
        make = lambda: jax.tree.map(  # noqa: E731
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        if shardings is None:
            return make()
        return jax.jit(make, out_shardings=shardings)()

    def _pin_cache_out(self, *tail, stage=None):
        """jit kwargs pinning a dispatch's donated-cache OUTPUT to
        the engine's explicit cache shardings (mesh engines; {} on
        single-device). Inputs arrive committed — the cache via
        _fresh_cache's device_put, params via
        shard_params_for_serving — so in_shardings are inferred from
        the operands; pinning the output closes the loop: the
        donated pool keeps its layout step over step and GSPMD never
        inserts a resharding collective on it (asserted by the
        pool_collective_lines guard test). `tail` holds one None per
        non-cache output — unconstrained, XLA places them. `stage`
        selects ONE stage's shardings for a staged engine's
        per-stage dispatch (the same zero-resharding pin, applied on
        that stage's submesh)."""
        if self._cache_shardings is None:
            return {}
        sh = (self._cache_shardings if stage is None
              else self._cache_shardings[stage])
        if tail:
            return {'out_shardings': (sh, *tail)}
        return {'out_shardings': sh}

    # -- staged (tensor x pipeline) engine ----------------------------------
    def _fresh_staged_cache(self):
        """Per-stage zeroed caches, one tree per stage submesh. Each
        stage's model owns only its [lo, hi) layers, so its cache tree
        holds the FULL page pool for just those layers — the per-stage
        pool split that lets an S-stage T-way mesh hold ~S·T x the
        pages at fixed per-chip HBM. Within a stage the placement is
        exactly the PR 15 tensor-parallel layout on the submesh."""
        from skypilot_tpu.parallel import serving as _tp_serving
        self._reset_paging()
        cfg = self.model.config
        page_kw = {'page_indices': jnp.zeros(
            (self.num_slots, self.pages_per_seq), jnp.int32)}
        first_shardings = self._cache_shardings is None
        if first_shardings:
            self._cache_shardings = []
        caches = []
        for s, sm in enumerate(self._stage_models):
            x = (jnp.zeros((self.num_slots, 1), jnp.int32) if s == 0
                 else jnp.zeros((self.num_slots, 1, cfg.embed_dim),
                                cfg.dtype))
            shapes = self._cache_shapes(sm, x, page_kw)
            if first_shardings:
                self._cache_shardings.append(
                    _tp_serving.serving_cache_shardings(
                        shapes, self._stage_submeshes[s]))
            caches.append(self._zeros(shapes, self._cache_shardings[s]))
        return caches

    def _stage_decode_fn(self, s: int):
        """One stage's jitted decode dispatch: stage 0 maps tokens ->
        hidden, middle stages hidden -> hidden, the last stage samples
        tokens from its logits. Shape-polymorphic through retracing —
        the plain loop calls with seq=1, the speculative verify chunk
        with seq=K+1, the group ring with batch=num_slots/stages."""
        key = ('decode', s)
        if key in self._stage_fns:
            return self._stage_fns[key]
        sm = self._stage_models[s]
        if s == self.stages - 1:

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._pin_cache_out(None, stage=s))
            def stage_fn(params, cache, x, positions, temps, top_ks,
                         top_ps, rng, page_indices, lora=None,
                         adapter_ids=None):
                extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                         if lora is not None else {})
                logits, mutated = sm.apply(
                    {'params': params, 'cache': cache}, x,
                    positions=positions, decode=True,
                    mutable=['cache'], page_indices=page_indices,
                    **extra)
                if logits.shape[1] == 1:
                    out = sample_tokens(rng, logits[:, 0], temps,
                                        top_ks, top_ps)
                else:           # verify chunk: [B, K+1, V]
                    out = sample_tokens(rng, logits, temps, top_ks,
                                        top_ps)
                return mutated['cache'], out
        else:

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._pin_cache_out(None, stage=s))
            def stage_fn(params, cache, x, positions, page_indices,
                         lora=None, adapter_ids=None):
                extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                         if lora is not None else {})
                hidden, mutated = sm.apply(
                    {'params': params, 'cache': cache}, x,
                    positions=positions, decode=True,
                    mutable=['cache'], page_indices=page_indices,
                    **extra)
                return mutated['cache'], hidden

        self._stage_fns[key] = stage_fn
        return stage_fn

    def _make_staged_decode_chain(self):
        """Host-side stage chain with the SAME signature as the
        single-mesh jitted decode/spec fns, so every dispatch call
        site works unchanged. Each stage's dispatch is async; the
        activation hops submeshes through an explicit device_put (the
        ONLY cross-stage traffic — per-stage pools never exchange a
        byte), and the ring-fed token array hops back to stage 0 the
        same way. The host never blocks inside the chain."""

        def decode_chain(params, cache, cur, pos, temps, top_ks,
                         top_ps, rng, page_indices=None, lora=None,
                         adapter_ids=None):
            cur = jnp.asarray(cur)
            pos = jnp.asarray(pos)
            if cur.ndim == 1:           # plain decode: seq=1
                x = cur[:, None]
                positions = pos[:, None]
            else:                       # speculative verify chunk
                x = cur
                positions = (pos[:, None] +
                             jnp.arange(cur.shape[1],
                                        dtype=jnp.int32)[None, :])
            lora_kw = ({'lora': lora, 'adapter_ids': adapter_ids}
                       if lora is not None else {})
            caches = []
            out = None
            for s in range(self.stages):
                x = jax.device_put(x, self._stage_replicated[s])
                fn = self._stage_decode_fn(s)
                with self._stage_submeshes[s]:
                    if s < self.stages - 1:
                        new_cache, x = fn(params[s], cache[s], x,
                                          positions, page_indices,
                                          **lora_kw)
                    else:
                        new_cache, out = fn(params[s], cache[s], x,
                                            positions, temps, top_ks,
                                            top_ps, rng, page_indices,
                                            **lora_kw)
                caches.append(new_cache)
            return caches, out

        # The chain only ever runs inside scheduler-thread dispatch
        # paths (it IS self._decode); pin the escape so the per-stage
        # fn cache's ownership holds.
        return decode_chain  # stpu: role[scheduler]

    def _stage_prefill_fn(self, s: int, bucket_len: int, fresh: bool):
        """One stage's jitted prefill-chunk dispatch (batch 1, a
        log2-bucketed chunk). `fresh` distinguishes a from-empty
        prefill (chunk-local attention) from a suffix chunk that
        attends the full resident history through the page table —
        the same prefill=True/False split as the single-mesh fns."""
        key = ('prefill', s, bucket_len, fresh)
        if key in self._stage_fns:
            return self._stage_fns[key]
        sm = self._stage_models[s]
        aligned = fresh or self._suffix_page_aligned
        if s == self.stages - 1:

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._pin_cache_out(None, stage=s))
            def stage_fn(params, cache, x, positions, plen, page_row,
                         lora=None, adapter_ids=None):
                extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                         if lora is not None else {})
                logits, mutated = sm.apply(
                    {'params': params, 'cache': cache}, x,
                    positions=positions, decode=True,
                    mutable=['cache'], page_indices=page_row,
                    prefill=fresh, page_aligned=aligned, **extra)
                # The continuation samples from the LAST REAL chunk
                # position, not the padded tail.
                last = jax.lax.dynamic_index_in_dim(
                    logits[0].astype(jnp.float32), plen - 1, axis=0,
                    keepdims=False)
                return mutated['cache'], last
        else:

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._pin_cache_out(None, stage=s))
            def stage_fn(params, cache, x, positions, page_row,
                         lora=None, adapter_ids=None):
                extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                         if lora is not None else {})
                hidden, mutated = sm.apply(
                    {'params': params, 'cache': cache}, x,
                    positions=positions, decode=True,
                    mutable=['cache'], page_indices=page_row,
                    prefill=fresh, page_aligned=aligned, **extra)
                return mutated['cache'], hidden

        self._stage_fns[key] = stage_fn
        return stage_fn

    def _staged_prefill_chain(self, bucket_len: int, fresh: bool):
        """Host-side prefill chain matching the single-mesh
        `_prefill_fn` (fresh=True) / `_prefill_suffix_fn`
        (fresh=False) signatures. Dispatches are async, so
        successive chunk microbatches PIPELINE across stages: chunk
        c+1's stage-0 pass runs while chunk c occupies stage 1 — the
        chunked-prefill stream is the microbatch stream, no separate
        schedule executor needed (the schedule's closed form only
        prices the bubble, see _prefill_work)."""

        def chain(params, cache, x_tokens, plen, *rest, lora=None,
                  adapter_ids=None):
            if fresh:
                (page_row,) = rest
                positions = jnp.arange(bucket_len,
                                       dtype=jnp.int32)[None, :]
            else:
                offset, page_row = rest
                positions = (offset +
                             jnp.arange(bucket_len,
                                        dtype=jnp.int32))[None, :]
            lora_kw = ({'lora': lora, 'adapter_ids': adapter_ids}
                       if lora is not None else {})
            x = jnp.asarray(x_tokens)[None, :]
            caches = []
            last = None
            for s in range(self.stages):
                x = jax.device_put(x, self._stage_replicated[s])
                fn = self._stage_prefill_fn(s, bucket_len, fresh)
                with self._stage_submeshes[s]:
                    if s < self.stages - 1:
                        new_cache, x = fn(params[s], cache[s], x,
                                          positions, page_row,
                                          **lora_kw)
                    else:
                        new_cache, last = fn(params[s], cache[s], x,
                                             positions, plen, page_row,
                                             **lora_kw)
                caches.append(new_cache)
            return caches, last

        # Same story as the decode chain: prefill chunks dispatch
        # only from the scheduler loop.
        return chain  # stpu: role[scheduler]

    # -- jitted device fns --------------------------------------------------
    def _make_decode_fn(self):
        if self.stages > 1:
            return self._make_staged_decode_chain()
        model = self.model

        # Donate the cache: the caller always replaces self.cache with
        # the result, so XLA updates in place instead of copying the
        # full KV cache every token (no-op on CPU, vital on TPU).
        # Donation is necessary, not sufficient: the KV write must also
        # keep the pool's layout (ops/paged_attention._write_pool) — a
        # scatter gets its own on TPU and two whole-pool copies.
        paged = self.paged

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._pin_cache_out(None))
        def decode(params, cache, cur_token, pos, temps, top_ks,
                   top_ps, rng, page_indices=None, lora=None,
                   adapter_ids=None, live=None):
            extra = {'page_indices': page_indices} if paged else {}
            if lora is not None:
                extra.update(lora=lora, adapter_ids=adapter_ids)
            if live is not None:
                extra.update(live=live[:, None])
            logits, mutated = model.apply(
                {'params': params, 'cache': cache},
                cur_token[:, None], positions=pos[:, None], decode=True,
                mutable=['cache'], **extra)
            # Per-slot temperature/top-k/top-p: greedy where temp==0.
            out = sample_tokens(rng, logits[:, 0], temps, top_ks,
                                top_ps)
            return mutated['cache'], out

        return decode

    def _make_chunk_decode_fn(self):
        """N single-token decode steps in ONE jitted dispatch: the
        whole chunk is a lax.scan whose carry is (cache, token, pos,
        rng). The rng chain is jax.random.split exactly as the
        step-by-step loop performs it, so sampled outputs are
        bit-identical; the host commits tokens afterwards, truncating
        at each slot's limit/eos/stop (post-finish writes are junk the
        next chunk or prefill overwrites before attending — the
        write-before-read contract shared with speculation)."""
        model = self.model
        paged = self.paged
        n = self.decode_chunk

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._pin_cache_out(None, None))
        def chunk_decode(params, cache, cur_token, pos, temps, top_ks,
                         top_ps, rng, page_indices=None, lora=None,
                         adapter_ids=None):
            extra = {'page_indices': page_indices} if paged else {}
            if lora is not None:
                extra.update(lora=lora, adapter_ids=adapter_ids)

            def step(carry, _):
                cache, tok, pos, rng = carry
                logits, mutated = model.apply(
                    {'params': params, 'cache': cache},
                    tok[:, None], positions=pos[:, None], decode=True,
                    mutable=['cache'], **extra)
                rng, sub = jax.random.split(rng)
                out = sample_tokens(sub, logits[:, 0], temps, top_ks,
                                    top_ps)
                return (mutated['cache'], out, pos + 1, rng), out

            (cache, _, _, rng), toks = jax.lax.scan(
                step, (cache, cur_token, pos, rng), None, length=n)
            return cache, toks, rng            # toks: [n, slots]

        return chunk_decode

    def _make_spec_decode_fn(self):
        """Verification step for prompt-lookup speculation: a
        [slots, K+1] chunk ([current, draft_1..draft_K] per row) runs
        through the model's chunked decode path in ONE call (paged:
        write_kv_chunk + paged_chunk_attention; dense:
        chunked_cache_attention) — between 1 and K+1 tokens commit per
        model call. Returns the model's own next-token choice at every
        chunk position; acceptance is computed host-side.

        Sampling stays EXACT: position t's token is sampled from
        p(. | prefix, draft_<t), and the host only commits it while
        every earlier draft matched the model's choice — so each
        committed token was sampled from the true conditional of the
        committed prefix (greedy is the temperature-0 special case).
        """
        if self.stages > 1:
            # The staged chain is shape-polymorphic: a [B, K+1] chunk
            # retraces the per-stage fns at seq=K+1 and the last
            # stage samples the whole chunk, exactly like the
            # single-mesh verify dispatch below.
            return self._make_staged_decode_chain()
        model = self.model
        paged = self.paged
        k = self.spec_k

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._pin_cache_out(None))
        def spec_decode(params, cache, chunk, pos, temps, top_ks,
                        top_ps, rng, page_indices=None, lora=None,
                        adapter_ids=None):
            positions = pos[:, None] + jnp.arange(k + 1)[None, :]
            extra = {'page_indices': page_indices} if paged else {}
            if lora is not None:
                extra.update(lora=lora, adapter_ids=adapter_ids)
            logits, mutated = model.apply(
                {'params': params, 'cache': cache}, chunk,
                positions=positions, decode=True, mutable=['cache'],
                **extra)                                   # [B, K+1, V]
            out = sample_tokens(rng, logits, temps, top_ks, top_ps)
            return mutated['cache'], out

        return spec_decode

    def _draft(self) -> 'np.ndarray':
        """Host-side prompt-lookup drafts [slots, K]: for each active
        slot, the K tokens that followed the most recent earlier
        occurrence of the trailing `spec_ngram` (context = committed
        output ++ pending current token); no match (or inactive) =
        repeat the last token (worst case: 1 commit per step, same as
        plain decode).

        The backward scan is bounded to the trailing `spec_lookback`
        tokens so host-side draft cost per decode round stays O(1) in
        the generation length (unbounded it is O(output_len) per round
        — quadratic overall — on the single scheduler thread)."""
        k, ngram = self.spec_k, self.spec_ngram
        drafts = np.zeros((self.num_slots, k), np.int32)
        for slot in range(self.num_slots):
            if not self.active[slot]:
                continue
            ctx = self.outputs[slot] + [int(self.cur_token[slot])]
            last = ctx[-1]
            drafts[slot, :] = last
            if len(ctx) <= ngram:
                continue
            pattern = ctx[-ngram:]
            floor = max(0, len(ctx) - self.spec_lookback)
            # Most recent strictly-earlier occurrence of the pattern.
            for start in range(len(ctx) - ngram - 1, floor - 1, -1):
                if ctx[start:start + ngram] == pattern:
                    cont = ctx[start + ngram:start + ngram + k]
                    if cont:
                        drafts[slot, :len(cont)] = cont
                        drafts[slot, len(cont):] = cont[-1]
                    break
        return drafts

    def _prefill_fn(self, bucket_len: int):
        """fn(params, cache, slot, prompt[P], plen) -> (cache, next_tok).

        CHUNKED prefill: ONE forward pass over the padded prompt
        that also writes every position's K/V (the model's
        decode-with-seq>1 mode) — not a per-token scan. Dense: runs on
        a batch-1 slice of the slot's cache rows, then scatters the
        rows back. Paged: the cache has no slot dimension — the pass
        runs on the full (donated) pool and writes only the slot's own
        pages via its page-table row; padded-tail writes land in
        allocated-but-masked slots or the trash page. Either way other
        slots are untouched, so prefill interleaves with the shared
        decode loop.
        """
        if bucket_len in self._prefill_fns:
            return self._prefill_fns[bucket_len]
        if self.stages > 1:
            fn = self._staged_prefill_chain(bucket_len, fresh=True)
            self._prefill_fns[bucket_len] = fn
            return fn
        model = self.model
        takes_live = self._takes_live
        positions = jnp.arange(bucket_len, dtype=jnp.int32)[None, :]
        if self.paged:

            @functools.partial(jax.jit, donate_argnums=(1,),
                               **self._pin_cache_out(None))
            def prefill_paged(params, cache, prompt, plen, page_row,
                              lora=None, adapter_ids=None, slot=None):
                # CHUNKED prefill: the whole (padded) prompt in ONE
                # forward pass; the model writes K/V for every
                # position (write_kv_chunk). Junk past plen lands in
                # allocated-but-masked slots or the trash page.
                # prefill=True: the sequence starts empty, attention
                # stays chunk-local.
                extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                         if lora is not None else {})
                if takes_live:
                    extra['live'] = positions < plen
                if slot is not None:
                    # State by slot: the row's slot (its state starts
                    # from zeros: prefill=True).
                    extra['slots'] = slot[None]
                logits, mutated = model.apply(
                    {'params': params, 'cache': cache},
                    prompt[None, :], positions=positions,
                    decode=True, mutable=['cache'],
                    page_indices=page_row, prefill=True,
                    page_aligned=True, **extra)
                # The continuation samples from the LAST REAL prompt
                # position, not the padded tail.
                last = jax.lax.dynamic_index_in_dim(
                    logits[0].astype(jnp.float32), plen - 1, axis=0,
                    keepdims=False)
                return mutated['cache'], last

            self._prefill_fns[bucket_len] = prefill_paged
            return prefill_paged

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._pin_cache_out(None))
        def prefill(params, cache, slot, prompt, plen, lora=None,
                    adapter_ids=None):
            extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                     if lora is not None else {})
            row = jax.tree.map(
                lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=0)
                if c.ndim else c, cache)
            row = jax.tree.map(
                lambda c: jnp.zeros_like(c) if c.ndim else c, row)
            # CHUNKED prefill on the batch-1 row (junk K/V past plen is
            # overwritten by later decode steps before the mask exposes
            # it), then scatter the row back. prefill=True: the row is
            # zeroed, so attention stays chunk-local (S x S,
            # flash-eligible) instead of S x max_seq_len scores.
            logits, mutated = model.apply(
                {'params': params, 'cache': row},
                prompt[None, :], positions=positions,
                decode=True, mutable=['cache'], prefill=True, **extra)
            row = mutated['cache']
            last = jax.lax.dynamic_index_in_dim(
                logits[0].astype(jnp.float32), plen - 1, axis=0,
                keepdims=False)
            cache = jax.tree.map(
                lambda big, small:
                jax.lax.dynamic_update_slice_in_dim(big, small, slot,
                                                    axis=0)
                if big.ndim else small, cache, row)
            return cache, last

        self._prefill_fns[bucket_len] = prefill
        return prefill

    def _prefill_suffix_fn(self, bucket_len: int):
        """fn(params, cache, suffix[P], suffix_len, offset, page_row)
        -> (cache, last_logits): chunked prefill of a prompt SUFFIX
        whose first `offset` tokens are already resident in (shared)
        KV pages. prefill=False — the chunk attends the FULL history
        through the page table (the speculative-verify attention
        path), and its writes land only at positions >= offset, i.e.
        never in a shared page."""
        key = ('suffix', bucket_len)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        if self.stages > 1:
            fn = self._staged_prefill_chain(bucket_len, fresh=False)
            self._prefill_fns[key] = fn
            return fn
        model = self.model
        aligned = self._suffix_page_aligned
        takes_live = self._takes_live

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._pin_cache_out(None))
        def prefill_suffix(params, cache, suffix, suffix_len, offset,
                           page_row, lora=None, adapter_ids=None,
                           slot=None):
            extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                     if lora is not None else {})
            positions = (offset +
                         jnp.arange(bucket_len, dtype=jnp.int32))[None, :]
            if takes_live:
                extra['live'] = positions < offset + suffix_len
            if slot is not None:
                extra['slots'] = slot[None]
            logits, mutated = model.apply(
                {'params': params, 'cache': cache},
                suffix[None, :], positions=positions,
                decode=True, mutable=['cache'],
                page_indices=page_row, prefill=False,
                page_aligned=aligned, **extra)
            last = jax.lax.dynamic_index_in_dim(
                logits[0].astype(jnp.float32), suffix_len - 1, axis=0,
                keepdims=False)
            return mutated['cache'], last

        self._prefill_fns[key] = prefill_suffix
        return prefill_suffix

    def _dense_suffix_fn(self, bucket_len: int):
        """fn(params, cache, slot, suffix[P], suffix_len, offset)
        -> (cache, last_logits): the dense-cache analog of
        `_prefill_suffix_fn` for chunked prefill. Runs the chunk on
        the slot's batch-1 cache row WITHOUT zeroing it (earlier
        chunks' K/V are the history), prefill=False so attention
        covers the full row through `offset` + the chunk itself
        (the chunked-cache-attention path speculation uses), then
        scatters the row back. Padded-tail writes land past the real
        suffix and are overwritten before any later step attends them
        (write-before-read)."""
        key = ('dense_suffix', bucket_len)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        model = self.model

        @functools.partial(jax.jit, donate_argnums=(1,),
                           **self._pin_cache_out(None))
        def dense_suffix(params, cache, slot, suffix, suffix_len,
                         offset, lora=None, adapter_ids=None):
            extra = ({'lora': lora, 'adapter_ids': adapter_ids}
                     if lora is not None else {})
            row = jax.tree.map(
                lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1,
                                                       axis=0)
                if c.ndim else c, cache)
            positions = (offset +
                         jnp.arange(bucket_len,
                                    dtype=jnp.int32))[None, :]
            logits, mutated = model.apply(
                {'params': params, 'cache': row},
                suffix[None, :], positions=positions,
                decode=True, mutable=['cache'], prefill=False, **extra)
            row = mutated['cache']
            last = jax.lax.dynamic_index_in_dim(
                logits[0].astype(jnp.float32), suffix_len - 1, axis=0,
                keepdims=False)
            cache = jax.tree.map(
                lambda big, small:
                jax.lax.dynamic_update_slice_in_dim(big, small, slot,
                                                    axis=0)
                if big.ndim else small, cache, row)
            return cache, last

        self._prefill_fns[key] = dense_suffix
        return dense_suffix

    # -- public API ---------------------------------------------------------
    def submit(self, prompt: List[int],
               max_new_tokens: int = 64,
               temperature: Optional[float] = None,
               top_k: int = 0, top_p: float = 1.0,
               stop_token_ids: Optional[List[int]] = None,
               on_token: Optional[Callable[[int], None]] = None,
               deadline_s: Optional[float] = None,
               adapter: Optional[str] = None,
               trace_ctx: Optional['tracing.Ctx'] = None
               ) -> 'Future':
        """Queue a request; the Future resolves to the full token list
        (prompt ++ generated). `temperature` overrides the engine
        default per request (0 = greedy); `top_k`/`top_p` filter the
        sampled distribution (0 / 1.0 = off); `stop_token_ids` end
        THIS request on any listed token (in addition to the engine's
        eos_id), with the stop token included in the output.

        `deadline_s` bounds the request's WHOLE life (queue wait +
        decode), in seconds from now: an expired request is reaped
        between decode rounds — whether still queued or mid-decode —
        and its Future raises DeadlineExceededError.

        Raises QueueSaturatedError (shed: the bounded queue is full)
        and EngineDeadError (the scheduler thread died) instead of
        queueing work that cannot be served.

        `adapter` names a LoRA adapter from the engine's adapter
        store (None = base model): the slot decodes with that
        adapter's factors gathered into the shared dispatch, its KV
        pages are keyed per-adapter in the prefix cache, and the
        adapter stays pinned in the device store until the request
        leaves its slot. Unknown names raise AdapterNotFoundError
        here (before queueing).

        `on_token` streams: called once per COMMITTED generated token,
        in order, on the scheduler thread — before the Future resolves
        — so it must be fast and non-blocking (push to a queue; don't
        do I/O). Tokens regenerated after a page-pressure preemption
        are not re-delivered (they became prompt on re-admission).

        `trace_ctx` attaches a distributed-tracing context
        (observability/tracing.py): the scheduler emits queue-wait /
        admission / prefill-chunk / decode-round spans under it. None
        (unsampled, the default) adds zero per-request work."""
        if self._dead.is_set():
            raise EngineDeadError(
                'engine scheduler thread is dead; restart the server')
        if len(prompt) >= self.max_total_len:
            raise ValueError(
                f'prompt len {len(prompt)} >= max_total_len '
                f'{self.max_total_len}')
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f'top_p must be in (0, 1], got {top_p}')
        if top_k < 0:
            raise ValueError(f'top_k must be >= 0, got {top_k}')
        if adapter is not None:
            if self.adapter_store is None:
                raise AdapterNotFoundError(
                    f'adapter {adapter!r} requested but this engine '
                    f'has no adapter store (serve_lm --adapter-dir)')
            # Inventory check only (404 fast); the load happens at
            # admission on the scheduler thread.
            self.adapter_store.resolve(adapter)
        with self._shed_lock:
            if self.max_queue_requests and \
                    self._queue.qsize() + len(self._ready) >= \
                    self.max_queue_requests:
                self.requests_shed += 1
                raise QueueSaturatedError(
                    f'queue full ({self.max_queue_requests} requests '
                    f'waiting); retry later')
            if self.max_queue_tokens and \
                    self._queued_tokens_n + len(prompt) > \
                    self.max_queue_tokens:
                self.requests_shed += 1
                raise QueueSaturatedError(
                    f'queued prompt tokens would exceed '
                    f'{self.max_queue_tokens}; retry later')
            self._queued_tokens_n += len(prompt)
        temp = self.temperature if temperature is None else temperature
        deadline = (time.monotonic() + float(deadline_s)
                    if deadline_s is not None else 0.0)
        fut: Future = Future()
        # `tref` carries (ctx, enqueue perf_counter): admission
        # observes every request's queue wait from it, and emits the
        # queue-wait span for a sampled one (ctx is None otherwise).
        # Positional invariants the rest of the scheduler relies on
        # survive: item[0] is the prompt, item[-2] the deadline,
        # item[-1] the future.
        tref = (trace_ctx, time.perf_counter())
        self._queue.put((list(prompt), int(max_new_tokens),
                         float(temp), int(top_k), float(top_p),
                         frozenset(stop_token_ids or ()), adapter,
                         tref, on_token, deadline, fut))
        return fut

    def cancel(self, futs) -> None:
        """Best-effort cancel of submitted requests (the client hung
        up mid-stream): an active slot finishes NOW with its output so
        far (freeing the slot instead of decoding tokens nobody will
        read); a queued request resolves without running. Thread-safe;
        applied by the scheduler between decode rounds."""
        with self._cancel_lock:
            self._cancel_requests.update(futs)

    def _apply_cancellations(self) -> None:
        with self._cancel_lock:
            if not self._cancel_requests:
                return
            cancels = self._cancel_requests
            self._cancel_requests = set()
        for slot in range(self.num_slots):
            if (self.active[slot] or self.prefilling[slot]) and \
                    self.futures[slot] in cancels:
                self._finish_slot(slot)
        # Requests still sitting in _queue (submitted after the last
        # _admit drain) must be swept too, or a disconnected client's
        # queued request is later admitted and decoded to completion.
        # Drain into _ready first — the same FCFS append _admit does —
        # then one sweep covers both.
        while True:
            try:
                self._ready.append(self._queue.get_nowait())
            except queue.Empty:
                break
        keep: 'collections.deque' = collections.deque()
        while self._ready:
            item = self._ready.popleft()
            if item[-1] in cancels:
                self._queued_tokens_sub(len(item[0]))
                item[-1].set_result(list(item[0]))  # prompt only
            else:
                keep.append(item)
        self._ready = keep

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)

    def kv_cache_bytes(self) -> int:
        """Device bytes of the slot pool's KV cache (paged pools:
        pages + scale arrays; dense: the per-slot rows) — the
        denominator of the quantized-serving memory math
        (skypilot_serving_kv_pool_bytes)."""
        # Metadata-only read (shape/dtype, never buffer contents):
        # safe from scrape threads even though the cache is donated.
        # State by slot is no KV cache: /stats `state_pool` has it.
        return int(sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(self.cache))  # stpu: ignore[SKY008]
            ) - self.state_pool_stats().get('bytes', 0)

    def state_pool_stats(self) -> Dict[str, Any]:
        """/stats `state_pool`: what a sequence keeps by slot beside
        its pages (arrays with a slot's row shape, the layers that
        have them, bytes a slot, slots, bytes); {} for a model whose
        sequences are their pages."""
        if not self.slot_state:
            return {}
        item = jnp.dtype(getattr(self.model.config, 'dtype',
                                 jnp.bfloat16)).itemsize
        return self.page_layout.describe_slots(self.num_slots, item)

    def kv_cache_bytes_per_device(self) -> int:
        """Bytes of the KV cache resident on ONE device: sharded pool
        values count a single shard, replicated leaves (scale pages,
        bookkeeping) count in full. Equals kv_cache_bytes() on a
        single device; ~1/mesh_devices of it when the kv-heads axis
        shards — the per-chip HBM figure --kv-pool-bytes budgets
        (skypilot_serving_kv_pool_bytes_per_device)."""
        # Staged engines: a chip belongs to exactly ONE stage, so the
        # per-chip figure is the WIDEST stage's per-device sum (the
        # layer remainder is front-loaded; other stages hold less).
        trees = self.cache if self.stages > 1 else [self.cache]  # stpu: ignore[SKY008]
        per_stage = []
        for tree in trees:
            total = 0
            # Metadata-only read, same story as kv_cache_bytes.
            for leaf in jax.tree_util.tree_leaves(tree):  # stpu: ignore[SKY008]
                sharding = getattr(leaf, 'sharding', None)
                shape = (sharding.shard_shape(leaf.shape)
                         if sharding is not None else leaf.shape)
                n = 1
                for d in shape:
                    n *= int(d)
                total += n * jnp.dtype(leaf.dtype).itemsize
            per_stage.append(total)
        return int(max(per_stage))

    def stage_pool_stats(self) -> List[Dict[str, Any]]:
        """Per-stage view of the staged KV pool for /stats: ONE
        shared allocator drives the whole stage chain, so every
        stage stores the SAME page indices (counts match), but each
        stage's pool materializes only its own [lo, hi) layer range
        — bytes track the layer split. Empty when stages == 1."""
        if self.stages <= 1:
            return []
        out: List[Dict[str, Any]] = []
        for s, (lo, hi) in enumerate(self._stage_ranges):
            total = 0
            # Metadata-only read, same story as kv_cache_bytes.
            for leaf in jax.tree_util.tree_leaves(self.cache[s]):  # stpu: ignore[SKY008]
                sharding = getattr(leaf, 'sharding', None)
                shape = (sharding.shard_shape(leaf.shape)
                         if sharding is not None else leaf.shape)
                n = 1
                for d in shape:
                    n *= int(d)
                total += n * jnp.dtype(leaf.dtype).itemsize
            out.append({'stage': s, 'layers': [lo, hi],
                        'pages': self.total_pages,
                        'pool_bytes_per_device': int(total)})
        return out

    def model_counters(self) -> Dict[str, Any]:
        """The model's device-side accumulators (`model.counter_leaves`:
        a routed model's tokens and touched calls per held expert, the
        sparse decode tokens), fetched now, as {leaf name: {block:
        nested lists}} ({} for a model without any). The arrays ride
        in the donated cache, so the read hops onto the scheduler
        thread and waits for the program in flight: only /stats asks."""
        names = tuple(getattr(self.model, 'counter_leaves', ()))
        if not names or self.stages > 1:
            return {}

        def op():
            flat, _ = jax.tree_util.tree_flatten_with_path(self.cache)
            picked = {}
            for path, leaf in flat:
                keys = [getattr(e, 'key', None) for e in path]
                if keys and keys[-1] in names:
                    picked[(keys[-1], '/'.join(keys[:-1]))] = leaf
            return jax.device_get(picked)

        out: Dict[str, Any] = {}
        for (name, block), value in self.run_on_scheduler(op).items():
            out.setdefault(name, {})[block] = np.asarray(value).tolist()
        return out

    def attention_impl(self) -> str:
        """The route this engine's traced decode read takes:
        ops/pallas_paged.resolve_impl given what that read gives it
        (whether the pool is int8, the K pool's static shape), so the
        name reported is the program compiled. 'dense' when the
        engine runs the dense per-slot cache — no paged read in play.
        Surfaced via the attention_impl_info gauge and /stats."""
        if not self.paged:
            return 'dense'
        from skypilot_tpu.ops import pallas_paged
        return pallas_paged.resolve_impl(
            quantized=self.kv_dtype == 'int8',
            decode_pool=self._pool_aval,
            layout=self.page_layout.kind)

    def chunk_attention_impl(self) -> str:
        """The route the attention of a whole `prefill_chunk` takes in
        this engine's traced prefill programs, beside
        `attention_impl()`'s decode read: for a latent pool
        ops/sparse_latent.chunk_route given what the model's chunk
        read gives it ('sparse_latent_pallas' where the kernel of
        ops/pallas_latent.py is compiled, 'sparse_latent_xla' for the
        walk: a refused shape, and a ladder's tail chunk of fewer
        queries than a tile, which /stats does not name); for a K/V
        pool `resolve_impl`'s answer for a read that passes no decode
        pool. 'dense' without a page pool. Surfaced in /stats."""
        if not self.paged:
            return 'dense'
        if self.page_layout.kind == 'latent':
            from skypilot_tpu.ops import sparse_latent
            cfg = self.model.config
            chunk = self.prefill_chunk or self.page_size
            return sparse_latent.chunk_route(
                jax.ShapeDtypeStruct(
                    (chunk, cfg.num_heads, self._pool_aval.shape[-1]),
                    self._pool_aval.dtype),
                self._pool_aval, self.pages_per_seq, cfg.kv_lora_rank)
        from skypilot_tpu.ops import pallas_paged
        return pallas_paged.resolve_impl(
            quantized=self.kv_dtype == 'int8',
            layout=self.page_layout.kind)

    def _compile_decode(self):
        """This engine's decode dispatch, lowered at its own shapes
        and compiled for the backend it serves on (scheduler thread:
        same mesh context and route selection as the live dispatch;
        with the persistent compile cache on, a cache read)."""
        n = self.num_slots
        args = [self.params, self.cache,
                jnp.zeros((n, self.spec_k + 1) if self.spec_k
                          else (n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.int32),
                jnp.ones((n,), jnp.float32),
                jax.random.PRNGKey(0)]
        if self.paged:
            args.append(jnp.asarray(self.page_table))
        return self._decode.lower(*args, **self._live_args()).compile()

    def decode_pool_collectives(self) -> Optional[List[str]]:
        """The zero-resharding guard (parallel/serving
        .pool_collective_lines) run on THIS engine's decode dispatch
        as compiled for the backend it serves on: HLO lines where an
        all-gather / all-to-all touches a pool-shaped operand
        ([] = the sharded pool stays put). None for staged engines
        (their per-stage dispatches are guarded in test_pp_serving)."""
        if self.stages > 1:
            return None
        if self.mesh is None:
            return []
        from skypilot_tpu.parallel import serving as _tp_serving

        def op():
            return _tp_serving.pool_collective_lines(
                self._compile_decode(), self.cache, self.mesh)

        return self.run_on_scheduler(op, timeout=1800.0)

    def pool_copy_lines(self) -> Optional[Dict[str, List[str]]]:
        """The in-place-write guard (parallel/serving.pool_copy_lines)
        on THIS engine's compiled decode dispatch and one prefill
        chunk (a whole `prefill_chunk` at a page-aligned offset, the
        suffix program): HLO lines where a `copy` produces a
        pool-shaped array, per program ([] = the donated pool is
        written where it lies). None for staged and dense engines.
        A bring-up check (chip_smoke.py), never on a request's path."""
        if self.stages > 1 or not self.paged:
            return None
        from skypilot_tpu.parallel import serving as _tp_serving

        def op():
            # The largest chunk a suffix at offset one page can be.
            chunk = min(self.prefill_chunk or self.page_size,
                        (self.pages_per_seq - 1) * self.page_size)
            suffix = self._prefill_suffix_fn(chunk).lower(
                self.params, self.cache,
                jnp.zeros((chunk,), jnp.int32), jnp.int32(chunk),
                jnp.int32(self.page_size),
                jnp.asarray(self.page_table[:1]),
                **({'slot': jnp.int32(0)} if self.slot_state else {})
                ).compile()
            return {
                'decode': _tp_serving.pool_copy_lines(
                    self._compile_decode(), self.cache),
                f'prefill_suffix_{chunk}': _tp_serving.pool_copy_lines(
                    suffix, self.cache)}

        return self.run_on_scheduler(op, timeout=1800.0)

    def attention_bytes_per_token(self) -> Dict[str, Any]:
        """Analytic HBM bytes one decode step moves per generated
        token at the CURRENT decode batch — the serve_bench roofline
        denominator (ops/pallas_paged.bytes_per_token_model, fed the
        engine's real page geometry, dtypes and adapter store). Dense
        engines model their full-cache walk with no dequant term."""
        from skypilot_tpu.ops import pallas_paged
        cfg = self.model.config
        if self.paged and self.page_layout.kind != 'kv':
            # No K/V heads to model: the row's own bytes over the whole
            # page table (what an index read walks), nothing else.
            item = jnp.dtype(getattr(cfg, 'dtype', jnp.bfloat16)).itemsize
            walked = self.pages_per_seq * self.page_size
            pool = (walked * self.page_layout.row_bytes(item)
                    * cfg.num_layers)
            return {'impl': self.attention_impl(),
                    'context_tokens_walked': walked,
                    'kv_pool_bytes': pool,
                    'total_bytes_per_token': float(pool)}
        if self._weight_bytes is None:
            from skypilot_tpu.inference import quant as quant_lib
            # Staged engines stream only ONE stage's weights per chip
            # per token: the widest stage bounds the roofline.
            self._weight_bytes = (
                max(quant_lib.weight_num_bytes(p) for p in self.params)
                if self.stages > 1
                else quant_lib.weight_num_bytes(self.params))
        lora_bytes = 0
        if self.adapter_store is not None:
            rank = int(getattr(self.adapter_store, '_rank', 0) or 0)
            targets = tuple(
                getattr(self.adapter_store, '_targets', ()) or ())
            if rank > 0 and targets:
                lora_bytes = lora_lib.adapter_num_bytes(cfg, rank,
                                                        targets)
        quantized = self.paged and self.kv_dtype == 'int8'
        elem = (1 if quantized else
                jnp.dtype(getattr(cfg, 'dtype', jnp.bfloat16)).itemsize)
        if self.paged:
            page_size, pages_per_seq = self.page_size, self.pages_per_seq
        else:
            page_size, pages_per_seq = 1, self.max_total_len
        # Per-stage layer split: a chip walks only its stage's layers'
        # KV pages (ceil — the widest stage, matching the weight term).
        pool_layers = ((self.page_layout.layers if self.paged else None)
                       or cfg.num_layers)
        num_layers = (-(-pool_layers // self.stages)
                      if self.stages > 1 else pool_layers)
        return pallas_paged.bytes_per_token_model(
            num_layers=num_layers,
            num_kv_heads=getattr(cfg, 'num_kv_heads', cfg.num_heads),
            num_q_heads=cfg.num_heads,
            head_dim=cfg.head_dim,
            page_size=page_size,
            pages_per_seq=pages_per_seq,
            kv_elem_bytes=elem,
            quantized=quantized,
            impl=self.attention_impl(),
            weight_bytes=self._weight_bytes,
            batch=max(int(self.active.sum()), 1),
            lora_bytes_per_row=lora_bytes)

    def update_metric_gauges(self) -> None:
        """Refresh the snapshot-style Prometheus gauges from live
        engine state. Called by the scrape handlers (/metrics and
        /stats) — reads race the scheduler thread harmlessly (numpy
        scalar reads; a stale value is one round old at worst)."""
        self.metrics.queue_depth.set(self._queue.qsize() +
                                     len(self._ready))
        self.metrics.active_slots.set(int(self.active.sum()))
        self.metrics.num_slots.set(self.num_slots)
        self.metrics.prefill_backlog.set(self.prefill_backlog_tokens())
        self.metrics.kv_pool_bytes.set(self.kv_cache_bytes())
        self.metrics.kv_pool_bytes_per_device.set(
            self.kv_cache_bytes_per_device())
        if self.paged:
            free = int(self.allocator.free_pages)
            self.metrics.pages_free.set(free)
            self.metrics.pages_used.set(self.total_pages - free)
        if self.kv_restore_lookups:
            self.metrics.kv_restore_hit_ratio.set(
                self.kv_restore_hits / self.kv_restore_lookups)
        self.metrics.pipeline_stages.set(self.stages)
        self.metrics.prefill_bubble_fraction.set(self._prefill_bubble)
        self.metrics.set_attention_info(self.attention_impl(),
                                        self.kv_dtype)
        self.metrics.attention_bytes_per_token.set(
            self.attention_bytes_per_token()['total_bytes_per_token'])
        for name, rec in self.phase_stats().items():
            self.metrics.set_phase_seconds(name, rec['s'])

    @property
    def decode_stall_s(self) -> float:
        """Seconds the scheduler stood blocked fetching a round's
        tokens: the `engine.fetch_wait` phase."""
        return self.phases.seconds('engine.fetch_wait')

    @property
    def loop_s(self) -> float:
        """Cumulative wall seconds of the scheduler loop's
        iterations (recovery included); the phases partition it."""
        return self.phases.inclusive('engine.loop')

    def phase_stats(self) -> Dict[str, Dict[str, Any]]:
        """{phase: {'n', 's'}} of the scheduler loop, self seconds;
        `loop_s` less their sum is what no phase covers (racy read:
        at worst one iteration apart)."""
        return {name: {'n': int(rec[0]), 's': round(rec[1], 6)}
                for name, rec in sorted(self.phases.totals.items())
                if name != 'engine.loop'}

    # -- KV page transfer + tiered cache ------------------------------------
    def run_on_scheduler(self, fn, timeout: float = 120.0):  # stpu: hop[scheduler]
        """Run `fn()` on the scheduler thread between rounds and
        return its result (exceptions re-raise here). The ONLY safe
        way to touch `self.cache` from another thread: every dispatch
        donates the cache buffer, so a concurrent gather/scatter from
        an HTTP thread would race the dispatch that consumes it.
        Calls made ON the scheduler thread run inline (control ops
        compose)."""
        if threading.current_thread() is self._thread:
            return fn()
        if self._dead.is_set():
            raise EngineDeadError(
                'engine scheduler thread is dead; restart the server')
        fut: Future = Future()
        self._control.put((fn, fut))
        return fut.result(timeout=timeout)

    def _run_control_ops(self) -> bool:
        """Drain pending control operations (start of each scheduler
        iteration). An op's failure resolves only ITS caller's future
        — unless it consumed the donated cache, which is the same
        unrecoverable condition as a failed dispatch and takes the
        full reset path."""
        ran = False
        while True:
            try:
                fn, fut = self._control.get_nowait()
            except queue.Empty:
                return ran
            ran = True
            try:
                fut.set_result(fn())
            except Exception as e:  # pylint: disable=broad-except
                fut.set_exception(e)
                if self._cache_lost():
                    raise

    def _refuse_slot_state(self, asked: Dict[str, bool]) -> None:
        """Refuse, by name, what a model with state by slot does not
        serve with yet (`asked`: {what: whether it was asked for})."""
        refused = [name for name, on in asked.items() if on]
        if refused:
            raise ValueError(
                f'{type(self.model.config).__name__} keeps recurrent '
                f'state by slot beside its pages '
                f'({", ".join(a.name for a in self.page_layout.slot_arrays)}) '
                f'and does not serve with {", ".join(refused)} yet: '
                f'a sequence is then more than its pages '
                f'(ROADMAP R-M5)')

    def _refuse_other_layouts(self, what: str) -> None:
        """The wire and spill formats pack K/V pages (and their int8
        scales) with their geometry; a pool of another layout refuses
        by name instead of shipping rows a peer would misread, and so
        does a model whose sequences keep state by slot: the pages
        alone would resume nothing."""
        if self.slot_state:
            self._refuse_slot_state({what: True})
        if self.paged and self.page_layout.kind != 'kv':
            raise ValueError(
                f'{what} packs K/V pages; the '
                f'{self.page_layout.kind!r} page layout of '
                f'{type(self.model.config).__name__} has no wire form '
                f'yet (ROADMAP R-M1)')

    def _gather_page_blobs(self, pages: List[int]
                           ) -> Dict[str, 'np.ndarray']:
        """Exact device bytes of physical pages `pages`, as
        {cache-leaf path: page-major host array} — the export side of
        handoff and spill. int8 pools gather int8 payload AND the f32
        scale rows; no dequantization anywhere (bit-identical round
        trip). Sharded pools gather per shard — the eager row gather
        runs on each device's own heads slice and the device_get
        assembles GLOBAL rows (the one place the export path pays a
        cross-device fetch; the decode path never does). Scheduler
        thread only."""
        from skypilot_tpu.ops import paged_attention as paged_ops
        self._refuse_other_layouts('a page export (handoff, migration, '
                                   'spill)')
        idx = jnp.asarray(pages, jnp.int32)
        # Staged engines: the per-stage trees use ABSOLUTE layer
        # names, so the union of their leaf paths IS the single-mesh
        # path set — the wire format is mesh-agnostic across stage
        # splits (stage-S exports import into stage-1 and back).
        trees = self.cache if self.stages > 1 else [self.cache]
        flat = []
        for tree in trees:
            flat.extend(jax.tree_util.tree_flatten_with_path(tree)[0])
        gathered = [paged_ops.gather_page_rows(leaf, idx)
                    for _path, leaf in flat]
        fetched = jax.device_get(gathered)
        return {jax.tree_util.keystr(path): np.asarray(arr)
                for (path, _), arr in zip(flat, fetched)}

    def _scatter_fn(self, m: int, stage: Optional[int] = None):
        key = m if stage is None else (m, stage)
        if key not in self._scatter_fns:
            from skypilot_tpu.ops import paged_attention as paged_ops

            @functools.partial(jax.jit, donate_argnums=(0,),
                               **self._pin_cache_out(stage=stage))
            def scatter(cache, idx, rows):
                return jax.tree.map(
                    lambda a, r: paged_ops.scatter_page_rows(a, idx,
                                                             r),
                    cache, rows)

            self._scatter_fns[key] = scatter
        return self._scatter_fns[key]

    def _scatter_page_blobs(self, pages: List[int],
                            blobs: Dict[str, 'np.ndarray']) -> None:
        """Write page-major host blobs into physical pages `pages`
        (import/restore). Chain lengths pad to a power of two so the
        jitted donating scatter compiles a log2 ladder, not one
        executable per length; pad rows target physical page 0 — the
        trash page, junk over junk. Staged engines route each leaf to
        its owning stage's pool (absolute layer names make the union
        of the stage trees the full single-mesh leaf set) and scatter
        per stage with that stage's donating pinned dispatch.
        Scheduler thread only."""
        staged = self.stages > 1
        trees = self.cache if staged else [self.cache]
        per_stage = []
        all_paths: List[str] = []
        for tree in trees:
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            paths = [jax.tree_util.keystr(p) for p, _ in flat]
            per_stage.append((flat, treedef, paths))
            all_paths.extend(paths)
        if sorted(all_paths) != sorted(blobs):
            raise ValueError(
                f'KV chain leaves do not match this engine\'s cache '
                f'layout (chain: {sorted(blobs)[:3]}..., cache: '
                f'{sorted(all_paths)[:3]}...)')
        n = len(pages)
        m = 1
        while m < n:
            m *= 2
        idx = np.zeros((m,), np.int32)
        idx[:n] = pages
        new_trees = []
        for s, (flat, treedef, paths) in enumerate(per_stage):
            rows = []
            for (_p, leaf), path in zip(flat, paths):
                arr = np.asarray(blobs[path])
                if leaf.ndim == 4:
                    want = (n, leaf.shape[0], leaf.shape[2],
                            leaf.shape[3])
                else:
                    want = (n, leaf.shape[1])
                if tuple(arr.shape) != want or \
                        arr.dtype != np.dtype(leaf.dtype):
                    raise ValueError(
                        f'KV chain leaf {path} is '
                        f'{arr.dtype}{arr.shape}, pool expects '
                        f'{np.dtype(leaf.dtype)}{want}')
                if m != n:
                    arr = np.concatenate(
                        [arr, np.zeros((m - n,) + arr.shape[1:],
                                       arr.dtype)], axis=0)
                rows.append(arr)
            rows_tree = jax.tree_util.tree_unflatten(treedef, rows)
            fn = self._scatter_fn(m, s if staged else None)
            new_trees.append(fn(trees[s], jnp.asarray(idx),
                                rows_tree))
        self.cache = new_trees if staged else new_trees[0]

    def export_chain(self, tokens: List[int],
                     adapter: Optional[str] = None
                     ) -> Optional[bytes]:
        """Serialize the prompt's cached full-page KV chain (payload
        + scales + adapter-salted chain keys + geometry) for handoff
        to another replica. Returns packed bytes covering the longest
        cached chain prefix, or None when nothing is cached (or
        prefix caching is off). Thread-safe: hops onto the scheduler
        thread; the chain is reference-pinned during the gather."""
        self._refuse_other_layouts('export_chain')
        if not self.prefix_caching:
            return None
        toks = [int(t) for t in tokens]

        def op():
            from skypilot_tpu.inference import kv_transfer
            cache = self.prefix_cache
            salt = b''
            if adapter is not None:
                if self.adapter_store is None:
                    raise AdapterNotFoundError(
                        f'adapter {adapter!r} requested for export '
                        f'but this engine has no adapter store')
                salt = self.adapter_store.cache_salt(adapter)
            keys = PrefixCache.chain_keys(toks, self.page_size,
                                          salt=salt)
            if not keys:
                return None
            pages = cache.lookup_acquire(keys, record=False)
            try:
                if not pages:
                    return None
                blobs = self._gather_page_blobs(pages)
            finally:
                cache.release(pages)
            # kv-head geometry rides the header (PR 15): blobs hold
            # GLOBAL page rows — _gather_page_blobs's device_get
            # assembles the shards — so a pool sharded a DIFFERENT
            # number of ways (or not at all) can validate and
            # rescatter them; the importing engine's own
            # out_shardings re-split the heads axis on its mesh.
            cfg = self.model.config
            meta = {'kind': 'kv_chain',
                    'kv_dtype': self.kv_dtype,
                    'page_size': self.page_size,
                    'num_kv_heads': int(getattr(cfg, 'num_kv_heads',
                                                0) or 0),
                    'head_dim': int(getattr(cfg, 'head_dim', 0) or 0),
                    'num_layers': int(getattr(cfg, 'num_layers',
                                              0) or 0),
                    'keys': [k.hex() for k in keys[:len(pages)]],
                    'salt': salt.hex()}
            packed = kv_transfer.pack_pages(blobs, meta)
            self.flight.record('handoff_export', pages=len(pages),
                               bytes=len(packed))
            return packed

        return self.run_on_scheduler(op)

    def import_chain(self, data: bytes) -> Dict[str, int]:
        """Scatter a packed page chain into this pool and register it
        in the prefix cache: the next submit of the same prompt (same
        adapter salt) admits against the imported pages instead of
        re-running prefill. Pages whose keys are already cached are
        skipped; pages that cannot fit even after spill-eviction are
        dropped (chain order — a dropped page also drops its
        suffix's usefulness, counted for the caller). Raises
        ValueError on any geometry/dtype mismatch. Thread-safe."""
        self._refuse_other_layouts('import_chain')
        if not self.prefix_caching:
            raise ValueError(
                'import_chain needs the paged engine with prefix '
                'caching enabled')

        def op():
            from skypilot_tpu.inference import kv_transfer
            meta, blobs = kv_transfer.unpack_pages(data)
            if meta.get('kind') != 'kv_chain':
                raise ValueError('not a KV chain payload')
            if meta.get('kv_dtype') != self.kv_dtype:
                raise ValueError(
                    f'kv_dtype mismatch: chain is '
                    f'{meta.get("kv_dtype")!r}, pool is '
                    f'{self.kv_dtype!r}')
            if int(meta.get('page_size', 0)) != self.page_size:
                raise ValueError(
                    f'page_size mismatch: chain is '
                    f'{meta.get("page_size")}, pool is '
                    f'{self.page_size}')
            # kv-head geometry (headers from PR-13 exporters lack it;
            # leaf-shape validation in _scatter_page_blobs still
            # catches those mismatches). Mesh SIZE is deliberately
            # not compared: chains carry global rows, so a tensor-2
            # export imports into a tensor-1 pool and back.
            cfg = self.model.config
            for field, want in (
                    ('num_kv_heads',
                     int(getattr(cfg, 'num_kv_heads', 0) or 0)),
                    ('head_dim',
                     int(getattr(cfg, 'head_dim', 0) or 0)),
                    # Layer count (PR 19): blobs carry one row per
                    # layer, so a layer-count mismatch would scatter
                    # rows into the wrong layers' pools. Stage SPLIT
                    # is deliberately not compared — blobs are keyed
                    # by absolute layer names, mesh-agnostic.
                    ('num_layers',
                     int(getattr(cfg, 'num_layers', 0) or 0))):
                got = meta.get(field)
                if got is not None and int(got) and want and \
                        int(got) != want:
                    raise ValueError(
                        f'{field} mismatch: chain is {got}, pool '
                        f'is {want}')
            keys = [bytes.fromhex(k) for k in meta.get('keys', [])]
            if len(keys) != int(meta.get('n_pages', -1)):
                raise ValueError('chain key count != page count')
            cache = self.prefix_cache
            todo = [(i, key) for i, key in enumerate(keys)
                    if key not in cache.by_key]
            already = len(keys) - len(todo)
            if todo:
                cache.evict_into(self.allocator, len(todo))
            fit = todo[:self.allocator.free_pages]
            dropped = len(todo) - len(fit)
            if fit:
                pages = self.allocator.allocate(len(fit))
                rows = {path: arr[[i for i, _ in fit]]
                        for path, arr in blobs.items()}
                try:
                    self._scatter_page_blobs(pages, rows)
                except Exception:
                    self.allocator.release(pages)
                    raise
                for (_i, key), page in zip(fit, pages):
                    cache.insert(key, page)
            self.flight.record('kv_import', pages=len(keys),
                               imported=len(fit),
                               already_cached=already,
                               dropped=dropped)
            return {'pages': len(keys), 'imported': len(fit),
                    'already_cached': already, 'dropped': dropped}

        return self.run_on_scheduler(op)

    def _evacuate_slot(self, slot: int, reason: str) -> Dict[str, Any]:
        """Evacuate ONE occupied slot (scheduler thread only): pack
        the committed-token KV chain, tear the slot down, and resolve
        its future with SessionMigratedError carrying everything a
        peer needs to finish the session. Mirrors _fail_slot's
        teardown order, with two migration twists: (1) the chain is
        gathered from the slot's LIVE page table (prefix+generated,
        not just the prompt chain export_chain covers); (2) before
        release, `slot_keys` is rewritten to the FULL committed chain
        so promote=True parks every exported page in the local prefix
        cache too — a failed ship falls back to warm local pages, not
        a cold replay. Mid-prefill slots ship no payload and never
        promote (pages past the frontier are unwritten junk)."""
        committed = [int(t) for t in self.outputs[slot]]
        adapter = self.slot_adapter_name[slot]
        was_prefilling = bool(self.prefilling[slot])
        payload = None
        n_chain = 0
        if self.paged and self.prefix_cache is not None and \
                not was_prefilling:
            salt = b''
            if adapter is not None and self.adapter_store is not None:
                salt = self.adapter_store.cache_salt(adapter)
            keys = PrefixCache.chain_keys(committed, self.page_size,
                                          salt=salt)
            if keys:
                try:
                    from skypilot_tpu.inference import kv_transfer
                    phys = [int(p) for p in
                            self.page_table[slot, :len(keys)]]
                    blobs = self._gather_page_blobs(phys)
                    cfg = self.model.config
                    meta = {'kind': 'kv_chain',
                            'kv_dtype': self.kv_dtype,
                            'page_size': self.page_size,
                            'num_kv_heads': int(getattr(
                                cfg, 'num_kv_heads', 0) or 0),
                            'head_dim': int(getattr(
                                cfg, 'head_dim', 0) or 0),
                            'num_layers': int(getattr(
                                cfg, 'num_layers', 0) or 0),
                            'keys': [k.hex() for k in keys],
                            'salt': salt.hex()}
                    payload = kv_transfer.pack_pages(blobs, meta)
                    n_chain = len(keys)
                except Exception:  # pylint: disable=broad-except
                    payload = None  # ship nothing; peer re-prefills
                # Full-chain promotion on teardown (see docstring).
                self.slot_keys[slot] = keys
        deadline = float(self.deadlines[slot])
        record = {
            'reason': reason,
            'tokens': committed,
            'prompt_len': int(self.prompt_len[slot]),
            'limit': int(self.limits[slot]),
            'temperature': float(self.temps[slot]),
            'top_k': int(self.top_ks[slot]),
            'top_p': float(self.top_ps[slot]),
            'stop_token_ids': sorted(self.stop_ids[slot]),
            'adapter': adapter,
            'deadline_s': (max(deadline - time.monotonic(), 0.5)
                           if deadline else 0.0),
            'payload': payload,
            'pages': n_chain,
        }
        fut = self.futures[slot]
        self.futures[slot] = None
        self.active[slot] = False
        self.on_tokens[slot] = None
        self.deadlines[slot] = 0.0
        self._slot_ctx[slot] = None
        self._release_adapter(slot)
        if was_prefilling:
            self.prefilling[slot] = False
            try:
                self._prefill_order.remove(slot)
            except ValueError:
                pass
        if self.paged:
            self._release_slot_pages(slot,
                                     promote=not was_prefilling)
        self.sessions_evacuated += 1
        if payload is not None:
            self.chains_evacuated += 1
        self.flight.record('evacuate', slot=slot, reason=reason,
                           pages=n_chain,
                           bytes=len(payload) if payload else 0)
        if fut is not None:
            fut.set_exception(SessionMigratedError(record))
        return record

    def _evacuate_queued(self, reason: str) -> int:
        """Fail every queued (not-yet-admitted) request with a
        payload-less SessionMigratedError: nothing should sit waiting
        on a dying replica when its caller can resubmit elsewhere
        immediately. Scheduler thread only."""
        while True:
            try:
                self._ready.append(self._queue.get_nowait())
            except queue.Empty:
                break
        n = 0
        while self._ready:
            (prompt, max_new, temp, top_k, top_p, stops, _adapter,
             _tref, _on_token, deadline, fut) = self._ready.popleft()
            self._queued_tokens_sub(len(prompt))
            record = {
                'reason': reason,
                'tokens': [int(t) for t in prompt],
                'prompt_len': len(prompt),
                'limit': min(len(prompt) + int(max_new),
                             self.max_total_len),
                'temperature': float(temp),
                'top_k': int(top_k),
                'top_p': float(top_p),
                'stop_token_ids': sorted(stops),
                'adapter': _adapter,
                'deadline_s': (max(deadline - time.monotonic(), 0.5)
                               if deadline else 0.0),
                'payload': None,
                'pages': 0,
            }
            fut.set_exception(SessionMigratedError(record))
            n += 1
        return n

    def evacuate_chains(self, max_sessions: Optional[int] = None,
                        reason: str = 'drain') -> Dict[str, int]:
        """Evacuate active sessions for live migration (drain,
        preemption notice, or rebalance): each occupied slot's
        committed tokens + packed KV chain come back to its waiting
        HTTP thread as a SessionMigratedError record, and the pages
        stay promoted in the LOCAL prefix cache as the warm fallback.
        `max_sessions=None` evacuates everything INCLUDING the queue
        (full drain); a bounded count (rebalance) takes the
        deepest-chain sessions first — most recompute saved per
        migration — and leaves the queue alone. Thread-safe: hops
        onto the scheduler thread. Returns
        {'evacuated', 'chains', 'queued'}."""
        self._refuse_other_layouts('live migration (evacuate_chains)')

        def op():
            evacuated = 0
            chains = 0
            limit_n = (self.num_slots if max_sessions is None
                       else max(int(max_sessions), 0))
            # Deepest committed sequence first: those chains cost the
            # most to recompute, so under a bounded budget they are
            # the ones worth shipping.
            order = sorted(
                (s for s in range(self.num_slots)
                 if self.active[s] or self.prefilling[s]),
                key=lambda s: -len(self.outputs[s]))
            for slot in order:
                if evacuated >= limit_n:
                    break
                rec = self._evacuate_slot(slot, reason)
                evacuated += 1
                if rec.get('payload') is not None:
                    chains += 1
            queued = (self._evacuate_queued(reason)
                      if max_sessions is None else 0)
            return {'evacuated': evacuated, 'chains': chains,
                    'queued': queued}

        return self.run_on_scheduler(op)

    def _restore_from_spill(self, keys: List[bytes],
                            shared: List[int]) -> None:
        """Extend the device-resident chain prefix from the spill
        tier, in place: for each key past the cached prefix (in chain
        order, stopping at the first miss), allocate a page, scatter
        the spilled bytes back, and acquire it exactly like a
        resident hit. Restored pages are bit-identical to the
        original compute — greedy continuations cannot tell."""
        from skypilot_tpu.inference import kv_transfer
        cache = self.prefix_cache
        # Restore only what can actually land: free pages plus the
        # evictable LRU. Fetching a chain the pool cannot hold wastes
        # host DMA AND churns the tier's own LRU for nothing.
        budget = self.allocator.free_pages + len(cache.lru)
        found_blobs = []
        found_keys = []
        for key in keys[len(shared):]:
            if len(found_blobs) >= budget:
                break
            self.kv_restore_lookups += 1
            blob = self.spill_tier.get(key)
            if blob is None:
                break
            self.kv_restore_hits += 1
            found_blobs.append(blob)
            found_keys.append(key)
        if not found_blobs:
            return
        cache.evict_into(self.allocator, len(found_blobs))
        n_fit = min(len(found_blobs), self.allocator.free_pages)
        if n_fit <= 0:
            return
        pages = self.allocator.allocate(n_fit)
        try:
            self._scatter_page_blobs(
                pages, kv_transfer.join_pages(found_blobs[:n_fit]))
        except Exception:
            self.allocator.release(pages)
            raise
        for key, page in zip(found_keys[:n_fit], pages):
            cache.acquire_page(key, page)
        shared.extend(pages)
        self.kv_restored_pages += n_fit
        self.metrics.kv_restore_pages.inc(n_fit)
        self.flight.record('restore', pages=n_fit)

    # -- scheduler loop -----------------------------------------------------
    def _loop(self) -> None:
        """Run iterations until stopped. Crash-only: if the thread is
        about to die for any reason other than stop() — including a
        non-Exception like an injected SystemExit — it first flips the
        dead flag and fails every pending future, so clients see
        EngineDeadError immediately instead of hanging on a silently
        absent scheduler (and /readyz reports 503)."""
        try:
            # Every dispatch traces on this thread, inside the mesh
            # context: that is where the paged-attention kernels look
            # for the `tensor` axis to shard_map over
            # (ops/pallas_paged.shard_over_kv_heads). Staged engines
            # enter each stage's own submesh around its dispatch.
            with (self.mesh if self.mesh is not None and self.stages == 1
                  else contextlib.nullcontext()):
                while not self._stop.is_set():
                    with tracing.phase('engine.loop', self.phases):
                        try:
                            self._iterate()
                            self._soft_errors = 0
                        except Exception as e:  # pylint: disable=broad-except
                            with tracing.phase('engine.recover',
                                               self.phases):
                                self._recover_from_error(e)
        finally:
            if not self._stop.is_set():
                self._dead.set()
                self.flight.record('death')
                self.flight.snapshot('death')
                died = EngineDeadError('engine scheduler thread died')
                for slot in range(self.num_slots):
                    fut = self.futures[slot]
                    self.futures[slot] = None
                    self.active[slot] = False
                    self.prefilling[slot] = False
                    self.on_tokens[slot] = None
                    self._release_adapter(slot)
                    if fut is not None and not fut.done():
                        fut.set_exception(died)
                self._fail_all_pending(died)
                while not self._control.empty():
                    try:
                        _fn, cfut = self._control.get_nowait()
                        cfut.set_exception(died)
                    except queue.Empty:
                        break

    def _iterate(self) -> None:
        """One iteration = admit (host-only) -> apply cancellations ->
        reap expired deadlines -> up to `prefill_budget` tokens of
        chunked prefill -> one decode round for the active slots. Long
        prompts therefore interleave with decoding instead of stalling
        it; with pipelining the decode round's host commit overlaps
        the NEXT round's device compute, and a prompt that finishes
        hands its first token to that round on the device
        (`_hand_first_tokens`), so the iteration waits for the device
        once, in the commit's fetch. The other decode loops fetch the
        first token before their round (`_sync_first_tokens`).

        Every stretch of it is one of the `engine.*` phases
        (docs/guides.md "Scheduler phases"): exclusive, and together
        the iteration's wall time."""
        phases = self.phases
        with tracing.phase('engine.control', phases):
            progressed = self._run_control_ops()
        with tracing.phase('engine.admit', phases):
            progressed = self._admit() or progressed
        with tracing.phase('engine.control', phases):
            self._apply_cancellations()
            self._reap_deadlines()
        if self._prefill_order:
            self._prefill_work()
            progressed = True
        if self.active.any() or self._inflight is not None or \
                any(f is not None for f in self._group_inflight):
            # The round's fetch_wait and commit phases nest in this
            # one, so its SELF time is the dispatch half and its whole
            # duration the decode step.
            with tracing.phase('engine.decode_dispatch',
                               phases) as step:
                committed0 = self.tokens_committed
                self._decode_step()
                self.flight.record(
                    'round_commit',
                    tokens=self.tokens_committed - committed0,
                    active=int(self.active.sum()))
            self.metrics.decode_step_seconds.observe(step.dur)
            if tracing.enabled():
                self._trace_decode_round(step.dur)
            progressed = True
        if not progressed and self._queue.empty() and \
                not self._ready:
            # Idle: block briefly for the next request. The
            # item goes straight into _ready — a get+put-back
            # would rotate the queue head to the TAIL,
            # inverting FCFS admission order.
            with tracing.phase('engine.idle_wait', phases):
                try:
                    self._ready.append(self._queue.get(timeout=0.05))
                except queue.Empty:
                    pass

    def _cache_lost(self) -> bool:
        """True when the donated KV cache buffer is gone (the device
        execution consumed it before failing): every slot's history is
        unrecoverable and only a full reset can continue. False means
        the exception fired BEFORE any device work touched the cache —
        state is consistent and serving can continue."""
        try:
            for leaf in jax.tree_util.tree_leaves(self.cache):
                deleted = getattr(leaf, 'is_deleted', None)
                if deleted is not None and deleted():
                    return True
            return False
        except Exception:  # pylint: disable=broad-except
            return True  # can't even inspect it: treat as lost

    def _recover_from_error(self, e: Exception) -> None:
        """Crash-only error containment, two tiers:

        CACHE INTACT (e.g. an injected fault or host-side error raised
        before the device dispatch): state is consistent — log, count,
        keep serving every slot; nothing is failed. A short fuse
        escalates repeated soft errors so a deterministic pre-dispatch
        failure cannot spin the loop forever.

        CACHE LOST (the donated buffer died inside the device call):
        fail the in-flight and queued requests loudly, reset the slots
        AND the cache, keep serving (the restart is counted in
        engine_restarts / skypilot_serving_engine_restarts_total)."""
        import traceback
        traceback.print_exc()
        self._soft_errors += 1
        self.soft_errors_total += 1
        victims = [s for s in range(self.num_slots)
                   if self.active[s] or self.prefilling[s]]
        self.flight.record('soft_error', error=type(e).__name__,
                           message=str(e)[:200],
                           strikes=self._soft_errors, slots=victims)
        if not self._cache_lost() and self._soft_errors < 3:
            print(f'engine {self.engine_id}: transient scheduler error '
                  f'({type(e).__name__}: {e}); state intact, '
                  f'continuing', flush=True)
            return
        self.flight.record('reset', error=type(e).__name__,
                           strikes=self._soft_errors, slots=victims,
                           restarts=self.engine_restarts + 1)
        self.flight.snapshot('reset')
        self.engine_restarts += 1
        self.metrics.engine_restarts.inc()
        self._soft_errors = 0
        self._inflight = None
        self._group_inflight = [None] * self.stages
        # First tokens not yet carried by a round die with the cache
        # they were sampled from (a fresh vector: the old one may hang
        # on the program that failed).
        self._first_pending[:] = False
        self._first_tokens = jnp.zeros((self.num_slots,), jnp.int32)
        try:
            self.cache = self._fresh_cache()
        except Exception:  # pylint: disable=broad-except
            traceback.print_exc()  # device truly gone
        for slot in range(self.num_slots):
            fut = self.futures[slot]
            self.futures[slot] = None
            self.active[slot] = False
            self.prefilling[slot] = False
            self.on_tokens[slot] = None
            self._slot_ctx[slot] = None
            self._release_adapter(slot)
            if fut is not None:
                fut.set_exception(e)
        self._prefill_order.clear()
        self.prefill_frontier[:] = 0
        self.prompt_len[:] = 0
        self.pos[:] = 0
        self.cur_token[:] = 0
        self.temps[:] = 0
        self.top_ks[:] = 0
        self.top_ps[:] = 1.0
        self.deadlines[:] = 0.0
        self._fail_all_pending(e)

    def _fail_all_pending(self, e: Exception) -> None:
        """Resolve every queued (not-yet-admitted) future with `e`."""
        while self._ready:
            prompt, *_rest, fut = self._ready.popleft()
            self._queued_tokens_sub(len(prompt))
            fut.set_exception(e)
        while not self._queue.empty():
            try:
                prompt, *_rest, fut = self._queue.get_nowait()
                self._queued_tokens_sub(len(prompt))
                fut.set_exception(e)
            except queue.Empty:
                break

    # -- deadlines / health / admission control -----------------------------
    def _queued_tokens_sub(self, n: int) -> None:
        with self._shed_lock:
            self._queued_tokens_n -= n

    def _queued_tokens_add(self, n: int) -> None:
        with self._shed_lock:
            self._queued_tokens_n += n

    def queued_requests(self) -> int:
        return self._queue.qsize() + len(self._ready)

    def queued_tokens(self) -> int:
        with self._shed_lock:
            return self._queued_tokens_n

    def healthy(self) -> bool:
        """Scheduler thread alive and processing (the /readyz
        signal)."""
        return not self._dead.is_set() and self._thread.is_alive()

    def saturated(self) -> bool:
        """Admission control would shed an (average-sized) request
        right now — surfaced by /readyz so load balancers steer
        traffic away BEFORE clients start eating 429s."""
        if self.max_queue_requests and \
                self.queued_requests() >= self.max_queue_requests:
            return True
        if self.max_queue_tokens and \
                self.queued_tokens() >= self.max_queue_tokens:
            return True
        return False

    def _release_adapter(self, slot: int) -> None:
        """Unpin the slot's adapter (if any) in the device store and
        account its committed tokens. Idempotent: the slot's adapter
        id is cleared on the first call."""
        aid = int(self.slot_adapter[slot])
        if not aid:
            return
        self.slot_adapter[slot] = 0
        self.slot_adapter_name[slot] = None
        if self.adapter_store is not None:
            n_gen = max(len(self.outputs[slot]) -
                        int(self.prompt_len[slot]), 0)
            self.adapter_store.release(aid, tokens=n_gen)

    def _fail_slot(self, slot: int, e: Exception) -> None:
        """Fail ONE slot's request (crash-only isolation): release its
        resources, resolve its future with `e`, keep every other slot
        running. Mid-prefill pages are never promoted (half-written)."""
        fut = self.futures[slot]
        self.futures[slot] = None
        self.active[slot] = False
        self.on_tokens[slot] = None
        self.deadlines[slot] = 0.0
        self._slot_ctx[slot] = None
        self._release_adapter(slot)
        if self.prefilling[slot]:
            self.prefilling[slot] = False
            try:
                self._prefill_order.remove(slot)
            except ValueError:
                pass
        if self.paged:
            self._release_slot_pages(slot, promote=False)
        if fut is not None:
            fut.set_exception(e)

    def _reap_deadlines(self) -> None:
        """Fail every expired request — queued or mid-decode — with
        DeadlineExceededError. Runs between rounds on the scheduler
        thread, so a reaped slot frees its pages before the next
        dispatch and an abandoned request never decodes to its limit."""
        now = time.monotonic()
        for slot in range(self.num_slots):
            dl = float(self.deadlines[slot])
            if dl and now > dl and (self.active[slot] or
                                    self.prefilling[slot]):
                self.deadline_exceeded += 1
                self._fail_slot(slot, DeadlineExceededError(
                    f'request deadline exceeded after '
                    f'{len(self.outputs[slot]) - int(self.prompt_len[slot])} '
                    f'generated tokens'))
        if not self._ready:
            return
        keep: 'collections.deque' = collections.deque()
        while self._ready:
            item = self._ready.popleft()
            deadline = item[-2]
            if deadline and now > deadline:
                self.deadline_exceeded += 1
                self._queued_tokens_sub(len(item[0]))
                item[-1].set_exception(DeadlineExceededError(
                    'request deadline exceeded while queued'))
            else:
                keep.append(item)
        self._ready = keep

    def _occupied(self) -> 'np.ndarray':
        return self.active | self.prefilling

    def _admit(self) -> bool:
        """Drain ready requests into free slots: prefix-cache lookup +
        page allocation + slot bookkeeping only — NO device work. The
        prompt suffix is prefilled by `_prefill_work` (chunked, under
        the token budget), which flips the slot PREFILLING -> active.
        """
        admitted = False
        while True:
            try:
                self._ready.append(self._queue.get_nowait())
            except queue.Empty:
                break
        while self._ready and not self._occupied().all():
            (prompt, max_new, temp, top_k, top_p, stops, adapter,
             tref, on_token, deadline, fut) = self._ready.popleft()
            t_adm = time.perf_counter()
            self._queued_tokens_sub(len(prompt))
            if deadline and time.monotonic() > deadline:
                # Expired while queued: prefilling it would only delay
                # live requests further.
                self.deadline_exceeded += 1
                fut.set_exception(DeadlineExceededError(
                    'request deadline exceeded while queued'))
                continue
            if max_new <= 0:
                fut.set_result(list(prompt))  # nothing to generate
                continue
            slot = int(np.argmin(self._occupied()))  # first free slot
            # Adapter resolution BEFORE page work: the store pins
            # (refcounts) the adapter for this slot's lifetime and
            # the prefix-cache keys below are salted with it.
            aid = 0
            salt = b''
            if adapter is not None:
                try:
                    aid = self.adapter_store.acquire(adapter)
                except Exception as e:  # pylint: disable=broad-except
                    # Missing/corrupt artifact or an injected
                    # adapters.load fault: fail THIS request (404/503
                    # at the HTTP layer); the engine keeps serving.
                    fut.set_exception(e)
                    continue
                if aid is None:
                    # Every device adapter slot is pinned by a running
                    # request: back to the HEAD (the page-pressure
                    # back-pressure contract) until one frees.
                    self._queued_tokens_add(len(prompt))
                    self._ready.appendleft(
                        (prompt, max_new, temp, top_k, top_p, stops,
                         adapter, tref, on_token, deadline, fut))
                    break
                salt = self.adapter_store.cache_salt(adapter)
            plen = len(prompt)
            shared: List[int] = []
            keys: List[bytes] = []
            if self.paged:
                # Prefix cache: map the prompt's cached full pages to
                # their existing physical pages; prefill computes only
                # the suffix. At least ONE token must prefill (the
                # continuation samples from its logits), so a fully
                # cached prompt drops its last shared page.
                if self.prefix_cache is not None:
                    keys = PrefixCache.chain_keys(prompt,
                                                  self.page_size,
                                                  salt=salt)
                    shared = self.prefix_cache.lookup_acquire(
                        keys, record=False)
                    # Tiered cache: evicted-then-spilled pages extend
                    # the resident prefix (restore == fresh compute,
                    # bit-identical) before the hit/miss accounting —
                    # a restored page avoided the recompute exactly
                    # like a resident hit.
                    if self.spill_tier is not None and \
                            len(shared) < len(keys):
                        n_res0 = len(shared)
                        t_res = time.perf_counter()
                        self._restore_from_spill(keys, shared)
                        if len(shared) > n_res0:
                            tracing.record_span(
                                'engine.kv_restore', tref[0],
                                time.perf_counter() - t_res,
                                pages=len(shared) - n_res0)
                    self.prefix_cache.record_lookup(
                        len(shared), len(keys) - len(shared))
                    if len(shared) * self.page_size >= plen:
                        self.prefix_cache.release([shared.pop()])
                n_cached = len(shared) * self.page_size
                # The prefill scan writes positions [n_cached, bucket):
                # the real suffix needs pages; the padded tail hits
                # trash only where the table row is unallocated, so
                # allocate for plen (+1 for the first generated token).
                need = self.allocator.pages_needed(plen + 1,
                                                   self.page_size) \
                    - len(shared)
                # Construction guarantees the pool holds one
                # full-depth sequence and submit() bounds plen below
                # max_total_len, so a lone sequence always fits.
                assert plen + 1 <= (self.total_pages - 1) * self.page_size
                if self.prefix_cache is not None:
                    self._evict_for(need, tref[0])
                if not self.allocator.can_allocate(need):
                    # Pool exhausted: back to the HEAD and stop
                    # admitting until a sequence releases pages —
                    # later arrivals must not starve this one.
                    if self.prefix_cache is not None:
                        self.prefix_cache.release(shared)
                    if aid:
                        self.adapter_store.release(aid)
                    self._queued_tokens_add(len(prompt))
                    self._ready.appendleft(
                        (prompt, max_new, temp, top_k, top_p, stops,
                         adapter, tref, on_token, deadline, fut))
                    break
                pages = self.allocator.allocate(need)
                self.owned_pages[slot] = pages
                self.shared_pages[slot] = shared
                self.slot_keys[slot] = keys
                self.page_table[slot, :] = 0
                self.page_table[slot, :len(shared)] = shared
                self.page_table[slot, len(shared):len(shared) + need] = \
                    pages
                self.allocated_tokens[slot] = (len(shared) + need) * \
                    self.page_size
            else:
                n_cached = 0
            # Claim the slot BEFORE any device work: if prefill raises,
            # the loop's exception handler finds (and fails) this
            # future instead of leaving the client hanging.
            self.futures[slot] = fut
            self.outputs[slot] = list(prompt)
            self.prompt_len[slot] = plen
            self.prefill_frontier[slot] = n_cached
            # While prefilling, `pos` rides the frontier: the decode
            # loop's junk write for this inactive lane lands exactly
            # where the NEXT prefill chunk writes (before attending).
            self.pos[slot] = n_cached
            self.cur_token[slot] = 0
            limit = min(plen + max_new, self.max_total_len)
            if self.paged:
                # The pool bounds the deepest any sequence can get
                # (minus chunk-write lookahead); admission would
                # otherwise hand out a limit the allocator can never
                # satisfy even running alone.
                limit = min(limit, (self.total_pages - 1) *
                            self.page_size - self._write_lookahead)
            self.limits[slot] = limit
            self.temps[slot] = temp
            self.top_ks[slot] = top_k
            self.top_ps[slot] = top_p
            self.stop_ids[slot] = stops
            self.on_tokens[slot] = on_token
            self.deadlines[slot] = deadline
            self.slot_adapter[slot] = aid
            self.slot_adapter_name[slot] = adapter if aid else None
            self.prefilling[slot] = True
            self._prefill_order.append(slot)
            self._prefill_t0[slot] = time.perf_counter()
            self._slot_ctx[slot] = tref[0]
            # Every request's queue wait (a preempted one observes
            # each of its waits); the spans only for a sampled one.
            self.metrics.queue_wait_seconds.observe(t_adm - tref[1])
            if tref[0] is not None:
                tracing.record_span('engine.queue_wait', tref[0],
                                    t_adm - tref[1], slot=slot)
                tracing.record_span('engine.admit', tref[0],
                                    self._prefill_t0[slot] - t_adm,
                                    slot=slot, prompt_len=plen,
                                    cached_tokens=n_cached)
            self.flight.record('admit', slot=slot, prompt_len=plen,
                               cached_tokens=n_cached,
                               queued=len(self._ready))
            self.metrics.admissions.inc()
            admitted = True
        return admitted

    def _evict_for(self, need: int, ctx) -> None:
        """Prefix-cache eviction for an admission, with an
        'engine.kv_spill' span when the admitting request is traced
        and the eviction actually ran (untraced requests call
        straight through: no clock reads)."""
        cache = self.prefix_cache
        if ctx is None:
            cache.evict_into(self.allocator, need)
            return
        ev0, sp0 = cache.evictions, cache.spilled_pages
        t0 = time.perf_counter()
        cache.evict_into(self.allocator, need)
        if cache.evictions > ev0:
            tracing.record_span(
                'engine.kv_spill', ctx,
                time.perf_counter() - t0,
                evicted=cache.evictions - ev0,
                spilled=cache.spilled_pages - sp0)

    # -- chunked prefill ----------------------------------------------------
    def _chunk_shape(self, n: int, offset: int) -> int:
        """Compiled shape for an n-real-token prefill chunk at
        `offset`. Full chunks reuse the ONE prefill_chunk shape; the
        final partial chunk (and the whole suffix when chunking is
        off) buckets to a power of two, capped by the chunk size —
        so the compile ladder is log2(prefill_chunk) shapes, not
        log2(max_total_len)."""
        cap = self.prefill_chunk or self.max_total_len
        shape = min(_bucket(n, cap), cap)
        if self.paged and offset:
            # The chunk writes positions [offset, offset + shape):
            # cap the shape so the padded tail cannot run past the
            # page-table row — take_along_axis CLAMPS an out-of-range
            # logical page to the last column, which is a REAL page
            # holding the prompt tail, and the scatter would shred it.
            shape = min(shape,
                        self.pages_per_seq * self.page_size - offset)
            assert shape >= n
        return shape

    def _run_prefill_chunk(self, slot: int, offset: int, n: int):
        """Dispatch ONE prefill chunk: n real tokens of slot's prompt
        at absolute position `offset`. Returns the (device) logits of
        the chunk's last real token — the continuation samples from
        them when this was the final chunk."""
        faults.point('engine.prefill_chunk')
        shape = self._chunk_shape(n, offset)
        chunk = self.outputs[slot][offset:offset + n]
        padded = jnp.asarray(chunk + [0] * (shape - n), jnp.int32)
        kw = self._slot_lora_args(slot)
        if self.slot_state:
            kw['slot'] = jnp.int32(slot)
        if self.paged and offset:
            fn = self._prefill_suffix_fn(shape)
            self.cache, last = fn(
                self.params, self.cache, padded, jnp.int32(n),
                jnp.int32(offset),
                jnp.asarray(self.page_table[slot:slot + 1]), **kw)
        elif self.paged:
            fn = self._prefill_fn(shape)
            self.cache, last = fn(
                self.params, self.cache, padded, jnp.int32(n),
                jnp.asarray(self.page_table[slot:slot + 1]), **kw)
        elif offset:
            fn = self._dense_suffix_fn(shape)
            self.cache, last = fn(
                self.params, self.cache, jnp.int32(slot), padded,
                jnp.int32(n), jnp.int32(offset), **kw)
        else:
            fn = self._prefill_fn(shape)
            self.cache, last = fn(
                self.params, self.cache, jnp.int32(slot), padded,
                jnp.int32(n), **kw)
        self.prefill_chunks_run += 1
        return last

    def _sample_first(self, slot: int, last_logits):
        """The continuation token from the final chunk's last-position
        logits: a device scalar, enqueued and not waited for."""
        temp = float(self.temps[slot])
        if temp > 0:
            self._rng, sub = jax.random.split(self._rng)
            return sample_tokens(
                sub, last_logits[None, :],
                jnp.full((1,), temp, jnp.float32),
                jnp.full((1,), int(self.top_ks[slot]), jnp.int32),
                jnp.full((1,), float(self.top_ps[slot]),
                         jnp.float32))[0]
        return jnp.argmax(last_logits)

    def _prefill_work(self) -> None:
        """Run at most `prefill_budget` suffix tokens of prefill, in
        prefill_chunk-sized dispatches, oldest admission first. Slots
        whose prompt completes sample their first token and join the
        decode loop; the budget bounds how long any single iteration
        defers the shared decode step (the anti-stall contract:
        chunked prefill never runs a dispatch longer than one chunk).
        With prefill_chunk=0 the whole suffix runs as ONE dispatch per
        slot (the legacy path) and the budget is unbounded."""
        budget = self.prefill_budget if self.prefill_chunk else None
        spent = 0
        chunks0 = self.prefill_chunks_run
        done: List[Any] = []    # (slot, last-position logits)
        while self._prefill_order:
            slot = self._prefill_order[0]
            plen = int(self.prompt_len[slot])
            offset = int(self.prefill_frontier[slot])
            n = plen - offset
            if self.prefill_chunk:
                n = min(n, self.prefill_chunk)
            if budget is not None and spent + n > budget:
                break   # budget spent: decode steps run first
            # One phase a chunk: the dispatch (which returns at
            # enqueue) and the slot's bookkeeping.
            with tracing.phase('engine.prefill_dispatch',
                               self.phases) as chunk:
                self.flight.record('chunk_dispatch', slot=slot,
                                   offset=offset, n=n)
                try:
                    last = self._run_prefill_chunk(slot, offset, n)
                except Exception as e:  # pylint: disable=broad-except
                    if self._cache_lost():
                        raise  # every slot's history died with the cache
                    # Crash-only isolation: the fault fired before the
                    # device touched the cache (e.g. an injected
                    # engine.prefill_chunk fault) — only THIS slot's
                    # request fails; the rest keep decoding untouched.
                    print(f'engine {self.engine_id}: prefill chunk '
                          f'for slot {slot} failed '
                          f'({type(e).__name__}: {e}); failing only '
                          f'that request', flush=True)
                    self.soft_errors_total += 1
                    self._fail_slot(slot, e)
                    continue
                spent += n
                offset += n
                self.prefill_frontier[slot] = offset
                self.pos[slot] = offset
                if offset >= plen:
                    self._prefill_order.popleft()
                    done.append((slot, last))
            self.metrics.prefill_chunk_seconds.observe(chunk.dur)
            tracing.record_span('engine.prefill_chunk',
                                self._slot_ctx[slot], chunk.dur,
                                slot=slot, offset=offset - n, n=n)
        self.last_prefill_tokens = spent
        if budget:
            self.metrics.prefill_budget_utilization.set(
                spent / budget)
        if self.stages > 1 and self.prefill_chunks_run > chunks0:
            # Closed-form bubble of this pass's chunk-microbatch
            # stream over the stage chain: M chunks through S stages
            # fill/drain (S-1)/(M+S-1) of the slot grid
            # (parallel/pipeline_schedule.make_inference_schedule —
            # the same span math the trainer's schedule asserts).
            from skypilot_tpu.parallel import pipeline_schedule
            sched = pipeline_schedule.make_inference_schedule(
                self.stages, self.prefill_chunks_run - chunks0)
            self._prefill_bubble = sched.bubble_fraction
        if not done:
            return
        # One phase a pass that finishes prompts: each one's sampling
        # enqueue (in completion order: the `_rng` splits follow it)
        # and its slot's activation, around the handoff of the token.
        with tracing.phase('engine.first_token_sync', self.phases):
            firsts = [(slot, self._sample_first(slot, last))
                      for slot, last in done]
            if self._defer_first:
                self._hand_first_tokens(firsts)
            else:
                self._sync_first_tokens(firsts)
            for slot, _ in firsts:
                self.pos[slot] = int(self.prompt_len[slot])
                self.prefilling[slot] = False
                self.active[slot] = True

    def _hand_first_tokens(self, firsts: List[Any]) -> None:
        """The plain pipelined loop's handoff: each first token stays
        on the device, in engine state, so that a dispatch retried
        after a fault finds it again. `_dispatch_round` feeds it to
        the lane's first round and `_commit_round` fetches it with
        that round's tokens: the scheduler waits for nothing here."""
        for slot, first in firsts:
            self._first_tokens = _stash_first_token(
                self._first_tokens, slot, first)
            self._first_pending[slot] = True
        self.first_tokens_deferred += len(firsts)
        self.metrics.first_tokens_deferred.inc(len(firsts))

    def _sync_first_tokens(self, firsts: List[Any]) -> None:
        """The blocking handoff of the loops that need `cur_token` on
        the host before their next dispatch (speculative drafts,
        decode chunks, the unpipelined step) or keep a ring of their
        own (stages): ONE device_get for every prompt the pass
        finished. The scheduler stands still for it, behind whatever
        the device still has queued."""
        tokens = jax.device_get([first for _, first in firsts])
        now = time.perf_counter()
        for (slot, _), first in zip(firsts, tokens):
            self.cur_token[slot] = int(first)
            self.metrics.prefill_seconds.observe(
                now - self._prefill_t0[slot])
        self.first_tokens_synced += len(firsts)
        self.metrics.first_tokens_synced.inc(len(firsts))

    def prefill_backlog_tokens(self) -> int:
        """Prompt-suffix tokens admitted but not yet prefilled (the
        chunked-prefill backlog; racy-but-harmless numpy reads, like
        the other scrape-time snapshots)."""
        return int(((self.prompt_len - self.prefill_frontier) *
                    self.prefilling).sum())

    def _grow_pages(self, lookahead: int = 1) -> None:
        """Before a decode step: every active slot about to write past
        its allocated tokens gets more pages (speculative chunks write
        `lookahead` tokens at once). On pool exhaustion the slot is
        PREEMPTED vLLM-style: its pages are released and the request
        re-queued with everything generated so far as the new prompt
        (recompute on re-admission), so page pressure stalls work
        instead of failing it. Requests that can never fit the pool
        fail loudly at admission. Sampled (temperature>0) requests may
        diverge across a preemption (fresh RNG); greedy decoding is
        unaffected."""
        preempted = []
        for slot in range(self.num_slots):
            if not self.active[slot]:
                continue
            # Clamp to the page-table row's capacity: the pipelined
            # loop's trailing round can write ONE position past a
            # finishing lane's limit, and legit writes never exceed
            # the table (construction headroom) — an out-of-capacity
            # junk write clamps into the lane's own released pages,
            # which every next owner rewrites before attending.
            need_tokens = min(int(self.pos[slot]) + lookahead,
                              self.pages_per_seq * self.page_size)
            exhausted = False
            while int(self.allocated_tokens[slot]) < need_tokens:
                # Allocation is logically contiguous: the next logical
                # page index == pages already allocated.
                logical = int(self.allocated_tokens[slot]) \
                    // self.page_size
                if not self.allocator.can_allocate(1) and \
                        self.prefix_cache is not None:
                    # Unreferenced cached prefixes yield before any
                    # live sequence gets preempted.
                    self.prefix_cache.evict_into(self.allocator, 1)
                if not self.allocator.can_allocate(1):
                    exhausted = True
                    break
                page = self.allocator.allocate(1)[0]
                self.owned_pages[slot].append(page)
                self.page_table[slot, logical] = page
                self.allocated_tokens[slot] += self.page_size
            if not exhausted:
                continue
            # Preempt: outputs-so-far become the prompt; the pending
            # cur_token is regenerated by the re-prefill. The adapter
            # ref drops with the slot (re-acquired — and reloaded if
            # evicted meanwhile — at re-admission).
            fut = self.futures[slot]
            adapter_name = self.slot_adapter_name[slot]
            remaining = int(self.limits[slot]) - len(self.outputs[slot])
            self.futures[slot] = None
            self.active[slot] = False
            self.preemptions += 1
            self.metrics.preemptions.inc()
            self.flight.record(
                'preempt', slot=slot,
                generated=len(self.outputs[slot]) -
                int(self.prompt_len[slot]))
            ctx = self._slot_ctx[slot]
            self._slot_ctx[slot] = None
            self._release_adapter(slot)
            self._release_slot_pages(slot, promote=False)
            if fut is not None:
                # The trace ctx rides the re-queued request: its
                # re-admission emits a second queue-wait span.
                tref = (ctx, time.perf_counter())
                preempted.append((list(self.outputs[slot]),
                                  max(remaining, 1),
                                  float(self.temps[slot]),
                                  int(self.top_ks[slot]),
                                  float(self.top_ps[slot]),
                                  self.stop_ids[slot],
                                  adapter_name, tref,
                                  self.on_tokens[slot],
                                  float(self.deadlines[slot]), fut))
                self._queued_tokens_add(len(self.outputs[slot]))
        # Back to the HEAD preserving pass order (repeated appendleft
        # would reverse it — an FCFS fairness inversion).
        self._ready.extendleft(reversed(preempted))

    def _release_slot_pages(self, slot: int, promote: bool) -> None:
        """Return a slot's pages: shared refs drop (page stays cached),
        own PROMPT-full pages are promoted into the prefix cache when
        `promote` (completion — their contents are final), the rest go
        back to the allocator. Preemption never promotes: its pages
        may hold half-written junk past the committed position."""
        cache = self.prefix_cache
        if cache is not None:
            own = self.owned_pages[slot]
            # Promote own pages BEFORE releasing the shared prefix
            # refs: LRU eviction pops oldest-first, and a chain is
            # only useful leaf-to-root — inserting leaves first makes
            # them evict before their prefixes (a prefix evicted
            # under a live suffix would orphan the suffix pages:
            # unreachable but resident).
            if promote and own:
                keys = self.slot_keys[slot]
                n_shared = len(self.shared_pages[slot])
                for i, page in enumerate(reversed(own)):
                    logical = n_shared + len(own) - 1 - i
                    if logical < len(keys) and \
                            cache.insert(keys[logical], page):
                        continue  # cache owns it now
                    self.allocator.release([page])
            else:
                self.allocator.release(own)
            cache.release(self.shared_pages[slot])
            self.shared_pages[slot] = []
            self.slot_keys[slot] = []
        else:
            self.allocator.release(self.owned_pages[slot])
        self.owned_pages[slot] = []
        self.page_table[slot, :] = 0
        self.allocated_tokens[slot] = 0

    def _emit(self, slot: int, tok: int) -> None:
        """Streaming callback for one committed token. A broken
        consumer (e.g. client hung up mid-stream) must not take down
        the shared scheduler loop: its callback is dropped and the
        request finishes normally."""
        cb = self.on_tokens[slot]
        if cb is None:
            return
        try:
            cb(tok)
        except Exception:  # pylint: disable=broad-except
            self.on_tokens[slot] = None

    def _finish_slot(self, slot: int) -> None:
        fut = self.futures[slot]
        self.futures[slot] = None
        self.active[slot] = False
        self.on_tokens[slot] = None
        self.deadlines[slot] = 0.0
        self._slot_ctx[slot] = None
        self._release_adapter(slot)
        was_prefilling = bool(self.prefilling[slot])
        if was_prefilling:
            # Cancelled mid-prefill: resolve with the prompt as-is
            # (nothing was generated) and drop the pending chunks.
            self.prefilling[slot] = False
            try:
                self._prefill_order.remove(slot)
            except ValueError:
                pass
        if self.paged:
            # Never promote a half-prefilled prompt's pages: pages
            # past the frontier were not written yet and would poison
            # the prefix cache.
            self._release_slot_pages(slot, promote=not was_prefilling)
        if fut is not None:
            fut.set_result(list(self.outputs[slot]))

    def _commit_token(self, slot: int, next_tok: int) -> bool:
        """Commit the slot's pending cur_token (append + stream +
        advance) and install `next_tok` as the new pending token;
        finish the slot (returning True) on limit/eos/stop. The ONE
        copy of the commit contract, shared by the plain, chunked,
        and speculative decode loops."""
        tok = int(self.cur_token[slot])
        self.outputs[slot].append(tok)
        self._emit(slot, tok)
        self.tokens_committed += 1
        self.metrics.tokens_committed.inc()
        self.pos[slot] += 1
        self.cur_token[slot] = int(next_tok)
        done = len(self.outputs[slot]) >= int(self.limits[slot])
        if self.eos_id is not None and tok == self.eos_id:
            done = True
        if tok in self.stop_ids[slot]:
            done = True
        if done:
            self._finish_slot(slot)
        return done

    def _lora_args(self) -> Dict[str, Any]:
        """Extra kwargs for a SHARED decode dispatch: the stacked
        adapter factors + per-slot adapter ids. {} when every lane is
        the base model — the zero-overhead fast path (the compiled
        base-only executables run untouched; the first adapter lane
        traces a second variant once)."""
        if self.adapter_store is None or not self.slot_adapter.any():
            return {}
        return {'lora': self.adapter_store.model_lora(),
                'adapter_ids': jnp.asarray(self.slot_adapter,
                                           jnp.int32)}

    def _live_args(self, staying: Optional[np.ndarray] = None
                   ) -> Dict[str, Any]:
        """`live` for a decode round of a model that takes it: the
        lanes that hold a request (the others ride with junk), less
        those the caller knows will have left it (`staying` False)."""
        if not self._takes_live:
            return {}
        live = self.active if staying is None else self.active & staying
        return {'live': jnp.asarray(live)}

    def _slot_lora_args(self, slot: int) -> Dict[str, Any]:
        """Extra kwargs for a batch-1 prefill dispatch of `slot`."""
        aid = int(self.slot_adapter[slot])
        if not aid:
            return {}
        return {'lora': self.adapter_store.model_lora(),
                'adapter_ids': jnp.asarray([aid], jnp.int32)}

    def _decode_step(self) -> None:
        # Injection point BEFORE any dispatch and before the round
        # consumes RNG: a raised fault leaves state untouched, so the
        # retried round produces bit-identical tokens (greedy AND
        # sampled) — the crash-only containment contract the chaos
        # suite locks in.
        faults.point('engine.decode_step')
        if self.spec_k:
            self._spec_decode_step()
            return
        if self.decode_chunk > 1:
            self._chunk_decode_step()
            return
        if self.pipeline_decode:
            if self.stages > 1:
                self._staged_pipelined_decode_step()
            else:
                self._pipelined_decode_step()
            return
        self._rng, sub = jax.random.split(self._rng)
        extra = ()
        if self.paged:
            self._grow_pages()
            if not self.active.any():
                return  # _grow_pages may have failed the last slot
            extra = (jnp.asarray(self.page_table),)
        # Every lane rides the round; an inactive one's token is
        # dropped at the commit. Its write lands at whatever `pos`
        # holds: an empty slot's last position (its page-table row is
        # zeroed on release, so a paged write goes to the trash page;
        # a dense row is zeroed by the next prefill), a PREFILLING
        # slot's frontier, which the next chunk overwrites before
        # attending.
        self.cache, sampled = self._decode(
            self.params, self.cache,
            jnp.asarray(self.cur_token), jnp.asarray(self.pos),
            jnp.asarray(self.temps), jnp.asarray(self.top_ks),
            jnp.asarray(self.top_ps), sub, *extra,
            **self._lora_args(), **self._live_args())
        sampled = self._fetch_tokens(sampled)
        self.decode_calls += 1
        self.metrics.decode_steps.inc()
        with tracing.phase('engine.commit', self.phases):
            for slot in range(self.num_slots):
                if not self.active[slot]:
                    continue
                self._commit_token(slot, int(sampled[slot]))

    def _trace_decode_round(self, dur: float) -> None:
        """One 'engine.decode_round' span per traced slot riding this
        round — the request's occupancy of the shared dispatch. Slots
        that finished inside the round already cleared their ctx (the
        final round is not attributed; the one-round skew is
        harmless)."""
        batch = int(self.active.sum())
        for slot in range(self.num_slots):
            ctx = self._slot_ctx[slot]
            if ctx is None or not (self.active[slot] or
                                   self.prefilling[slot]):
                continue
            tracing.record_span('engine.decode_round', ctx, dur,
                                slot=slot, pos=int(self.pos[slot]),
                                batch=batch)

    def _fetch_tokens(self, dev) -> 'np.ndarray':
        """device_get with decode-stall accounting: the wall time the
        host spends blocked here is exactly the serial host/device
        bubble pipelining exists to hide. `dev` is a round's token
        array, or a tuple of same-shaped arrays fetched together and
        returned stacked."""
        faults.point('engine.device_get')
        with tracing.phase('engine.fetch_wait', self.phases) as wait:
            out = np.asarray(jax.device_get(dev))
        stall = wait.dur
        self.metrics.decode_stall_seconds.inc(stall)
        if tracing.enabled():
            # The stall is shared by the whole round: attribute ONE
            # span to the first traced active slot (a representative,
            # not a per-slot fan-out).
            for slot in range(self.num_slots):
                ctx = self._slot_ctx[slot]
                if ctx is not None and self.active[slot]:
                    tracing.record_span(
                        'engine.device_get', ctx, stall,
                        stall_ms=round(stall * 1e3, 3))
                    break
        return out

    # -- pipelined decode ---------------------------------------------------
    def _dispatch_round(self, inflight: Optional[Dict[str, Any]]
                        ) -> Optional[Dict[str, Any]]:
        """Dispatch the next decode round WITHOUT waiting for the
        in-flight one or for a prompt's last chunk: continuing lanes
        feed the in-flight round's (device-resident) sampled tokens
        straight back as inputs — no host round-trip — at position
        +1; lanes that joined since take their first token from the
        handoff vector, on the device too (`_hand_first_tokens`); any
        other lane rides with the host's `cur_token`. A lane the
        pending commit will retire gets a junk write one past its
        last position (write-before-read keeps it harmless); where the
        in-flight token is the last its limit allows, that is known
        now, and a model that takes `live` is told the lane is dead."""
        if self.paged:
            # +1 lookahead when a round is still uncommitted: this
            # dispatch writes at pos+1 for continuing lanes.
            self._grow_pages(lookahead=2 if inflight is not None
                             else 1)
            if not self.active.any():
                return None
        joined = self._first_pending & self.active
        if inflight is None:
            cont = np.zeros((self.num_slots,), bool)
            pos = self.pos.copy()
            sampled = self._first_tokens    # no lane reads it
        else:
            cont = np.array(
                [bool(inflight['mask'][s]) and bool(self.active[s])
                 and self.futures[s] is inflight['futs'][s]
                 for s in range(self.num_slots)])
            pos = np.where(cont, inflight['pos'] + 1,
                           self.pos).astype(np.int32)
            sampled = inflight['sampled']
        staying = None
        if self._takes_live:
            staying = np.array(
                [not cont[s] or
                 len(self.outputs[s]) + 1 < int(self.limits[s])
                 for s in range(self.num_slots)])
        cur = _merge_cur_tokens(cont, sampled, joined,
                                self._first_tokens, self.cur_token)
        extra = (jnp.asarray(self.page_table),) if self.paged else ()
        self._rng, sub = jax.random.split(self._rng)
        self.cache, sampled = self._decode(
            self.params, self.cache, cur, jnp.asarray(pos),
            jnp.asarray(self.temps), jnp.asarray(self.top_ks),
            jnp.asarray(self.top_ps), sub, *extra,
            **self._lora_args(), **self._live_args(staying))
        # This round carries the joined lanes' first tokens; its
        # commit fetches them from the vector as it stands now.
        self._first_pending[:] = False
        self.decode_calls += 1
        self.metrics.decode_steps.inc()
        return {'sampled': sampled, 'mask': self.active.copy(),
                'pos': pos, 'futs': list(self.futures),
                'joined': joined, 'first_tokens': self._first_tokens}

    def _commit_round(self, inflight: Dict[str, Any]) -> None:
        """Fetch + commit a dispatched round. Lanes whose request
        finished, was preempted, or was replaced since dispatch are
        discarded (their round-N+1 token belongs to nobody). A lane
        that joined in this round learns its first token here, in the
        same fetch: the host's `cur_token` and the admission-to-
        first-token histogram take it before the lane's first commit
        streams it."""
        joined = inflight['joined']
        firsts = None
        if joined.any():
            sampled, firsts = self._fetch_tokens(
                (inflight['sampled'], inflight['first_tokens']))
        else:
            sampled = self._fetch_tokens(inflight['sampled'])
        now = time.perf_counter()
        with tracing.phase('engine.commit', self.phases):
            for slot in range(self.num_slots):
                if not inflight['mask'][slot]:
                    continue
                if not self.active[slot] or \
                        self.futures[slot] is not inflight['futs'][slot]:
                    continue
                if joined[slot]:
                    self.cur_token[slot] = int(firsts[slot])
                    self.metrics.prefill_seconds.observe(
                        now - self._prefill_t0[slot])
                self._commit_token(slot, int(sampled[slot]))

    def _pipelined_decode_step(self) -> None:
        """One pipelined iteration: dispatch round N+1 FIRST (device
        starts computing), then fetch + commit round N while N+1 runs
        — stop-detection, streaming callbacks, and future resolution
        all overlap device compute. Greedy outputs are token-for-token
        the unpipelined loop's: committed tokens come from the same
        round sequence; only the trailing round after a drain is
        speculative waste."""
        inflight = self._inflight
        nxt = self._dispatch_round(inflight) if self.active.any() \
            else None
        if inflight is not None:
            self._commit_round(inflight)
        self._inflight = nxt

    # -- staged pipelined decode (the S-deep ring) --------------------------
    def _group_slice(self, g: int) -> slice:
        width = self.num_slots // self.stages
        return slice(g * width, (g + 1) * width)

    def _dispatch_group_round(self, g: int,
                              inflight: Optional[Dict[str, Any]]
                              ) -> Optional[Dict[str, Any]]:
        """_dispatch_round on one slot GROUP: the width-W slice of
        the slot arrays rides the S-stage chain while the other
        groups' rounds occupy other stages. Same ring-feedback
        contract as the unstaged path — continuing lanes feed the
        in-flight round's device-resident tokens straight back."""
        sl = self._group_slice(g)
        if not self.active[sl].any():
            return None
        if inflight is None:
            cur = jnp.asarray(self.cur_token[sl])
            pos = self.pos[sl].copy()
        else:
            base = sl.start
            cont = np.array(
                [bool(inflight['mask'][i]) and
                 bool(self.active[base + i]) and
                 self.futures[base + i] is inflight['futs'][i]
                 for i in range(sl.stop - sl.start)])
            pos = np.where(cont, inflight['pos'] + 1,
                           self.pos[sl]).astype(np.int32)
            cur = jnp.where(jnp.asarray(cont), inflight['sampled'],
                            jnp.asarray(self.cur_token[sl]))
        self._rng, sub = jax.random.split(self._rng)
        self.cache, sampled = self._decode(
            self.params, self.cache, cur, jnp.asarray(pos),
            jnp.asarray(self.temps[sl]), jnp.asarray(self.top_ks[sl]),
            jnp.asarray(self.top_ps[sl]), sub,
            jnp.asarray(self.page_table[sl]),
            **self._group_lora_args(sl))
        self.decode_calls += 1
        self.metrics.decode_steps.inc()
        return {'sampled': sampled, 'mask': self.active[sl].copy(),
                'pos': pos, 'futs': list(self.futures[sl])}

    def _group_lora_args(self, sl: slice) -> Dict[str, Any]:
        """_lora_args for one slot group's width-W dispatch."""
        if self.adapter_store is None or \
                not self.slot_adapter[sl].any():
            return {}
        return {'lora': self.adapter_store.model_lora(),
                'adapter_ids': jnp.asarray(self.slot_adapter[sl],
                                           jnp.int32)}

    def _commit_group_round(self, g: int,
                            inflight: Dict[str, Any]) -> None:
        """Fetch + commit one group's dispatched round (lane i is
        slot g*W + i); discard rules match _commit_round."""
        sampled = self._fetch_tokens(inflight['sampled'])
        base = self._group_slice(g).start
        with tracing.phase('engine.commit', self.phases):
            for i in range(len(sampled)):
                slot = base + i
                if not inflight['mask'][i]:
                    continue
                if not self.active[slot] or \
                        self.futures[slot] is not inflight['futs'][i]:
                    continue
                self._commit_token(slot, int(sampled[i]))

    def _staged_pipelined_decode_step(self) -> None:
        """One iteration of the S-deep decode ring: slots partition
        into `stages` contiguous groups; dispatch EVERY group's next
        round through the stage chain first (async — group g+1's
        stage-0 pass overlaps group g's stage-1 pass, so the S
        in-flight rounds occupy different stages simultaneously),
        then fetch + commit each group's previous round. Greedy
        outputs are token-for-token the unpipelined loop's: each
        lane's successive rounds are still sequential."""
        self._grow_pages(lookahead=2)
        nxt: List[Optional[Dict[str, Any]]] = []
        for g in range(self.stages):
            nxt.append(self._dispatch_group_round(
                g, self._group_inflight[g]))
        for g in range(self.stages):
            if self._group_inflight[g] is not None:
                self._commit_group_round(g, self._group_inflight[g])
        self._group_inflight = nxt

    def _chunk_decode_step(self) -> None:
        """One chunked round: decode_chunk tokens for every active
        slot in ONE dispatch; commit host-side, truncating each slot
        at its limit/eos/stop (a finished slot's remaining chunk
        tokens are discarded — up to N-1 wasted steps, the price of
        amortizing dispatch overhead)."""
        n = self.decode_chunk
        extra = ()
        if self.paged:
            # The chunk writes positions pos..pos+n-1 (+1 commit room).
            self._grow_pages(lookahead=n)
            if not self.active.any():
                return
            extra = (jnp.asarray(self.page_table),)
        was_active = self.active.copy()
        self.cache, toks, self._rng = self._chunk_decode(
            self.params, self.cache, jnp.asarray(self.cur_token),
            jnp.asarray(self.pos), jnp.asarray(self.temps),
            jnp.asarray(self.top_ks), jnp.asarray(self.top_ps),
            self._rng, *extra, **self._lora_args())
        toks = self._fetch_tokens(toks)               # [n, slots]
        self.decode_calls += 1
        self.metrics.decode_steps.inc()
        with tracing.phase('engine.commit', self.phases):
            for slot in range(self.num_slots):
                if not was_active[slot]:
                    continue
                for i in range(n):
                    if self._commit_token(slot, int(toks[i, slot])):
                        break  # finished: discard the chunk's tail

    def _spec_decode_step(self) -> None:
        """One speculative round: draft K tokens per slot (host-side
        prompt lookup), verify the whole [current ++ drafts] chunk in
        ONE model call, commit the model-confirmed prefix — 1..K+1
        tokens per call. Rejected drafts leave stale cache entries
        above the new position; the next chunk overwrites them before
        attending (the chunked-attention write-before-read contract)."""
        k = self.spec_k
        drafts = self._draft()                         # [slots, K]
        extra = ()
        if self.paged:
            # The chunk writes positions pos..pos+K: allocate K+1 ahead.
            self._grow_pages(lookahead=k + 1)
            if not self.active.any():
                return
            extra = (jnp.asarray(self.page_table),)
        chunk = np.concatenate([self.cur_token[:, None], drafts], axis=1)
        self._rng, sub = jax.random.split(self._rng)
        self.cache, y = self._decode(
            self.params, self.cache, jnp.asarray(chunk),
            jnp.asarray(self.pos), jnp.asarray(self.temps),
            jnp.asarray(self.top_ks), jnp.asarray(self.top_ps), sub,
            *extra, **self._lora_args())
        y = self._fetch_tokens(y)                      # [slots, K+1]
        self.decode_calls += 1
        self.metrics.decode_steps.inc()
        with tracing.phase('engine.commit', self.phases):
            for slot in range(self.num_slots):
                if not self.active[slot]:
                    continue
                accept = 0
                while (accept < k and
                       int(drafts[slot, accept]) == int(y[slot, accept])):
                    accept += 1
                # Commit: the pending current token, then every
                # accepted draft; each commit's successor is the
                # model's own token for that position (y), so the
                # final pending token is the first correction. (The
                # accepted-prefix invariant makes cur_token equal the
                # next commit at every step, so the shared
                # _commit_token applies unchanged.)
                for nxt in y[slot, :accept + 1]:
                    if self._commit_token(slot, int(nxt)):
                        break
