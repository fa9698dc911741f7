"""Inference runtime: model loading, request execution, metrics.

Owns everything the HTTP layer needs to run a request: the model +
placed params, the per-(batch, temperature, length) one-shot jit
buckets, the optional continuous-batching engine, a streaming path
(engine token callbacks; a small lazy engine backs streaming when the
server runs in one-shot mode), and serving metrics (TTFT / e2e
latency percentiles surfaced by /stats — the BASELINE.md north-star
"p50 TTFT" is measured here).
"""
from __future__ import annotations

import collections
import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple


class NoTokenizerError(ValueError):
    """The served model has no tokenizer (a registry model; --hf
    checkpoints bring theirs)."""


class ServingMetrics:
    """Request metrics, thread-safe, dual-exported:

      - JSON `/stats` percentiles over a ROLLING WINDOW of the last
        `window` (default 1024) requests — `*_p50`/`*_p95` keys move
        as old requests age out;
      - Prometheus histograms/counters on `GET /metrics` covering the
        WHOLE process lifetime (observability/catalog.py).

    TTFT is the first COMMITTED token: streamed requests latch it at
    the first streamed token, non-streaming engine-backed requests at
    the first decode-step commit (catalog.FirstTokenLatch). One-shot
    (non-engine, non-streaming) requests have no per-token signal and
    record no TTFT. Inter-token gaps come from streamed requests
    only, measured per request row."""

    def __init__(self, window: int = 1024) -> None:
        from skypilot_tpu.observability import catalog as obs_catalog
        self._lock = threading.Lock()
        self.window = window
        self.ttft_ms: 'collections.deque' = collections.deque(
            maxlen=window)
        self.itl_ms: 'collections.deque' = collections.deque(
            maxlen=window)
        self.latency_ms: 'collections.deque' = collections.deque(
            maxlen=window)
        self.completion_tokens: 'collections.deque' = collections.deque(
            maxlen=window)
        self.requests = 0
        self.requests_shed = 0
        self.deadline_exceeded = 0
        self.prom = obs_catalog.RequestMetrics()
        # Declarative SLO accounting (observability/slo.py), attached
        # by build_runtime when --slo is set: every record()/
        # record_shed()/record_deadline_exceeded()/record_inter_token()
        # also feeds the burn-rate tracker. None = no SLO declared.
        self.slo = None

    def record(self, latency_s: float, n_tokens: int,
               ttft_s: Optional[float] = None,
               n_prompt_tokens: int = 0) -> None:
        with self._lock:
            self.requests += 1
            self.latency_ms.append(latency_s * 1000.0)
            self.completion_tokens.append(n_tokens)
            if ttft_s is not None:
                self.ttft_ms.append(ttft_s * 1000.0)
        self.prom.requests.inc()
        self.prom.e2e_latency_seconds.observe(latency_s)
        self.prom.completion_tokens.inc(max(n_tokens, 0))
        self.prom.prompt_tokens.inc(max(n_prompt_tokens, 0))
        if ttft_s is not None:
            self.prom.ttft_seconds.observe(ttft_s)
        if self.slo is not None:
            self.slo.record_request(
                ttft_ms=(ttft_s * 1000.0 if ttft_s is not None
                         else None))

    def record_shed(self) -> None:
        """One request rejected 429 by admission control."""
        with self._lock:
            self.requests_shed += 1
        self.prom.requests_shed.inc()
        if self.slo is not None:
            self.slo.record_request(shed=True)

    def record_deadline_exceeded(self) -> None:
        """One request answered 504 (expired queued or mid-decode)."""
        with self._lock:
            self.deadline_exceeded += 1
        self.prom.deadline_exceeded.inc()
        if self.slo is not None:
            self.slo.record_request(error=True)

    def record_inter_token(self, gap_s: float) -> None:
        """One gap between consecutive streamed tokens of a request
        row, measured at ENGINE COMMIT time (StreamHandle.on_token on
        the scheduler thread) — not at SSE frame delivery, which rides
        pump-thread scheduling and TCP flush batching and can inflate
        tail gaps by an order of magnitude under load."""
        with self._lock:
            self.itl_ms.append(gap_s * 1000.0)
        self.prom.inter_token_seconds.observe(gap_s)
        if self.slo is not None:
            self.slo.record_itl(gap_s * 1000.0)

    @staticmethod
    def _pct(values: List[float], q: float) -> Optional[float]:
        """Linear-interpolated percentile (numpy's default method).
        Nearest-rank reporting at small N made distinct percentiles
        collapse onto the same sample (p95 == p99 with 60 requests),
        which misreads as a flat tail; interpolation keeps them
        distinct and converges to the same values at large N."""
        if not values:
            return None
        s = sorted(values)
        pos = q * (len(s) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(s) - 1)
        frac = pos - lo
        return round(s[lo] * (1.0 - frac) + s[hi] * frac, 2)

    def snapshot(self) -> Dict[str, object]:
        """JSON stats. Window semantics: every `*_p50`/`*_p95` key and
        `gen_tokens_per_sec` cover the last `window` requests (see
        `window` key); `requests` counts the process lifetime. TTFT
        keys cover streamed + engine-backed non-streaming requests;
        `itl_ms_*` cover streamed requests only."""
        with self._lock:
            lat = list(self.latency_ms)
            ttft = list(self.ttft_ms)
            itl = list(self.itl_ms)
            toks = list(self.completion_tokens)
            n = self.requests
            shed = self.requests_shed
            expired = self.deadline_exceeded
        total_s = sum(lat) / 1000.0
        return {
            'requests': n,
            'requests_shed': shed,
            'deadline_exceeded': expired,
            'window': self.window,
            # Sample counts per latency block: percentiles over a
            # handful of samples are noise — consumers (benches,
            # dashboards) can qualify them.
            'ttft_ms_n': len(ttft),
            'itl_ms_n': len(itl),
            'latency_ms_n': len(lat),
            'ttft_ms_p50': self._pct(ttft, 0.50),
            'ttft_ms_p95': self._pct(ttft, 0.95),
            'ttft_ms_p99': self._pct(ttft, 0.99),
            'itl_ms_p50': self._pct(itl, 0.50),
            'itl_ms_p95': self._pct(itl, 0.95),
            'itl_ms_p99': self._pct(itl, 0.99),
            'latency_ms_p50': self._pct(lat, 0.50),
            'latency_ms_p95': self._pct(lat, 0.95),
            'completion_tokens_total': sum(toks),
            'gen_tokens_per_sec': round(sum(toks) / total_s, 2)
            if total_s > 0 else None,
        }


class StreamHandle:
    """Consumer side of one streaming request: committed tokens arrive
    on `q` (pushed from the engine scheduler thread); `future` resolves
    to the full prompt++generated list when the request finishes.
    `first_token_s` latches the TTFT instant and consecutive commits
    record inter-token gaps (the serving ITL signal, measured at the
    commit itself rather than at SSE delivery). Constructed BEFORE the
    engine submit so the very first committed token always finds the
    queue (the scheduler thread races the submitting thread)."""

    def __init__(self, metrics: Optional[ServingMetrics] = None
                 ) -> None:
        self.q: 'queue.Queue' = queue.Queue()
        self.future: Optional['Future'] = None  # set right after submit
        self.t0 = time.monotonic()
        self.first_token_s: Optional[float] = None
        # Monotonic instant of the first commit: the HTTP layer times
        # that token's way to the socket from it.
        self.first_token_t: Optional[float] = None
        self._metrics = metrics
        self._last_token_t: Optional[float] = None

    def on_token(self, tok: int) -> None:
        now = time.monotonic()
        if self.first_token_s is None:
            self.first_token_s = now - self.t0
            self.first_token_t = now
        elif self._metrics is not None:
            self._metrics.record_inter_token(now - self._last_token_t)
        self._last_token_t = now
        self.q.put(tok)


def iter_interleaved(handles: List[StreamHandle]):
    """Yield (choice_index, token) across streams in arrival order
    until every stream completes — one slow choice must not stall its
    siblings' chunks. Re-raises the engine's exception on failure.
    The shared poll loop behind every SSE endpoint (done-detection
    order matters: Empty -> future.done() -> q.empty() re-check closes
    the commit/resolve race window)."""
    done = [False] * len(handles)
    while not all(done):
        progressed = False
        for i, h in enumerate(handles):
            if done[i]:
                continue
            try:
                tok = h.q.get_nowait()
            except queue.Empty:
                if h.future.done() and h.q.empty():
                    h.future.result()  # raise to the caller on error
                    done[i] = True
                    progressed = True
                continue
            progressed = True
            yield i, int(tok)
        if not progressed:
            time.sleep(0.005)


#: What a token stream's terminal event may carry (--stream-final).
STREAM_FINAL = ('rows', 'lengths')


class InferenceRuntime:
    """Everything needed to execute generation requests.

    `engine` is the continuous-batching engine when the server runs in
    that mode, else None; `stream_engine()` always returns an engine
    (lazily building a small one in one-shot mode) because streaming
    needs per-token commit callbacks, which only the slot engine has.
    """

    def __init__(self, *, model, params, vocab_size: int,
                 model_name: str, max_total_len: int, spec_total: int,
                 speculative: int, engine=None,
                 engine_total: Optional[int] = None,
                 tokenizer_dir: Optional[str] = None,
                 stream_slots: int = 2,
                 prefill_chunk: int = 0,
                 prefill_budget: int = 0,
                 pipeline_decode: Optional[bool] = None,
                 request_timeout: float = 600.0,
                 max_queue_requests: int = 0,
                 max_queue_tokens: int = 0,
                 adapters=None,
                 kv_dtype: str = 'bf16',
                 weight_dtype: str = 'bf16',
                 role: str = '',
                 decode_peers: Optional[List[str]] = None,
                 mesh=None, stream_final: str = 'rows') -> None:
        import jax
        if stream_final not in STREAM_FINAL:
            raise ValueError(f'stream_final {stream_final!r}: one of '
                             f'{STREAM_FINAL}')
        # What a token stream's terminal event carries
        # (http_server: --stream-final).
        self.stream_final = stream_final
        self.model = model
        self.params = params
        # Tensor-parallel serving mesh (None = single device): the
        # engines' KV pools shard over it; /stats `storage` reports
        # mesh_devices so operators can audit per-chip pool math.
        self.mesh = mesh
        self.mesh_devices = (int(mesh.devices.size)
                             if mesh is not None else 1)
        # Pipeline-parallel stage count (--stages; 1 = no split):
        # /stats `storage.stages` alongside mesh_devices, so
        # tensor_ways = mesh_devices / stages.
        self.stages = (int(mesh.shape.get('stage', 1))
                       if mesh is not None else 1)
        # Disaggregated serving (docs/guides.md "Disaggregated
        # serving & cache tiering"): '' = unified replica (the
        # classic mode), 'decode' labels a decode-pool member,
        # 'prefill' additionally hands finished prompts' KV page
        # chains off to a decode peer instead of decoding locally.
        if role not in ('', 'unified', 'prefill', 'decode'):
            raise ValueError(f'unknown serving role {role!r}')
        self.role = '' if role == 'unified' else role
        self._peers_lock = threading.Lock()
        self._decode_peers: List[str] = []
        self._peer_ring = None
        self._handoff_lock = threading.Lock()
        self.handoffs_total = 0
        self.handoff_failures = 0
        self.handoff_bytes_total = 0
        self.kv_imports_total = 0
        self.kv_imported_pages_total = 0
        from skypilot_tpu.observability import catalog as _obs
        self._handoff_seconds = _obs.histogram(
            'skypilot_serving_kv_handoff_seconds')
        self._handoff_bytes = _obs.counter(
            'skypilot_serving_kv_handoff_bytes_total')
        # Live KV-chain migration (PR 20): out-migration counts by
        # trigger reason, evacuation totals, and the bounded ring of
        # affinity keys migrated IN — /stats exposes the ring so the
        # fleet controller can pin those sessions' follow-ups to this
        # replica at the LB.
        self._migration_lock = threading.Lock()
        self.migrations_by_reason: Dict[str, int] = {}
        self.migration_failures = 0
        self.sessions_evacuated_total = 0
        self.chains_evacuated_total = 0
        self.tokens_recomputed_total = 0
        self.migrations_in_total = 0
        self._migrated_in_keys: 'collections.OrderedDict[str, None]' \
            = collections.OrderedDict()
        # Evacuation hint: set by /kv/evacuate (controller-supplied
        # target + reason), read by the HTTP threads whose futures
        # resolve with SessionMigratedError moments later. Expires so
        # a stale rebalance hint can't redirect a later drain.
        self._evac_hint: Optional[Dict[str, object]] = None
        self._migration_seconds = _obs.histogram(
            'skypilot_serving_migration_seconds')
        self._chains_evacuated = _obs.counter(
            'skypilot_serving_chains_evacuated_total')
        self._tokens_recomputed = _obs.counter(
            'skypilot_serving_tokens_recomputed_total')
        if decode_peers:
            self.set_decode_peers(decode_peers)
        # Quantized-serving storage formats (inference/quant.py +
        # the model config's kv_dtype) — /stats and the
        # skypilot_serving_storage_info series report them.
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        from skypilot_tpu.inference import quant as quant_lib
        self.weight_bytes = quant_lib.weight_num_bytes(params)
        # Multi-LoRA adapter registry (inference/adapters.py) shared
        # by every engine in this runtime; None = base model only.
        self.adapters = adapters
        self.vocab_size = vocab_size
        self.model_name = model_name
        self.max_total_len = max_total_len
        self.spec_total = spec_total
        self.speculative = speculative
        self.engine = engine
        # engine_total overrides when the constructed engine's
        # capacity differs from the derived default (decode-chunk
        # clamp) — limit_for/advertised capacity must match what
        # engine.submit actually accepts.
        self.engine_total = engine_total if engine_total is not None \
            else (spec_total if speculative > 0 else max_total_len)
        self.tokenizer_dir = tokenizer_dir
        self.metrics = ServingMetrics()
        # Declared serving SLO (observability/slo.py), attached by
        # build_runtime when --slo is set; /stats renders its
        # burn-rate snapshot. None = no SLO declared.
        self.slo_tracker = None

        self._fns: Dict[Tuple[int, float, int], object] = {}
        self._lock = threading.Lock()
        self._rng = jax.random.PRNGKey(0)
        self._tok_holder: Dict[str, object] = {}
        self._tok_lock = threading.Lock()
        self._stream_engine = None
        self._stream_engine_lock = threading.Lock()
        self._stream_slots = stream_slots
        # Stall-free-scheduler knobs, reused by the lazy stream
        # engine so one-shot-mode streaming gets the same behavior.
        self._prefill_chunk = prefill_chunk
        self._prefill_budget = prefill_budget
        self._pipeline_decode = pipeline_decode
        # Robustness knobs: the server-wide request-deadline ceiling
        # (per-request `timeout` fields clamp to it) and the bounded
        # queue the lazy stream engine shares with the main one.
        self.request_timeout = float(request_timeout)
        self._max_queue_requests = max_queue_requests
        self._max_queue_tokens = max_queue_tokens

    # -- capacity -----------------------------------------------------------
    def limit_for(self, temperature: float,
                  streaming: bool = False) -> int:
        """Max total length the request class will actually run at.
        Streaming always runs through a slot engine built at
        engine_total — validate against THAT capacity, not the
        one-shot bucket's (they differ in one-shot+speculative mode)."""
        if self.engine is not None or streaming:
            return self.engine_total
        if self.speculative > 0 and temperature == 0.0:
            return self.spec_total
        return self.max_total_len

    # -- disaggregated prefill/decode ---------------------------------------
    def set_decode_peers(self, peers: List[str]) -> None:
        """Install the decode pool this prefill replica hands off to
        (endpoint strings 'host:port'). Pushed by the fleet
        controller via POST /kv/peers whenever the decode ready set
        changes; also settable statically with --decode-peers. The
        peer ring is the SAME consistent-hash mapping the LB's
        prefix-affinity policy uses over the same endpoint strings,
        so a handed-off session's follow-up requests (routed by the
        LB directly to the decode pool) land on the replica that
        already holds the imported pages."""
        from skypilot_tpu.serve import \
            load_balancing_policies as lb_policies
        peers = list(dict.fromkeys(str(p) for p in peers if p))
        ring = None
        if peers:
            ring = lb_policies.PrefixAffinityPolicy()
            ring.set_ready_replicas(peers)
        with self._peers_lock:
            self._decode_peers = peers
            self._peer_ring = ring

    def decode_peers(self) -> List[str]:
        with self._peers_lock:
            return list(self._decode_peers)

    def pick_decode_peer(self, key: Optional[str]) -> Optional[str]:
        """Handoff target for an affinity key: the ring's owner (the
        replica the LB would also pick for this session), else the
        first peer for keyless prompts."""
        with self._peers_lock:
            peers = list(self._decode_peers)
            ring = self._peer_ring
        if not peers:
            return None
        if key is not None and ring is not None:
            target = ring.affinity_target(key)
            if target is not None:
                return target
        return peers[0]

    def record_handoff(self, seconds: float, nbytes: int,
                       ok: bool) -> None:
        with self._handoff_lock:
            self.handoffs_total += 1
            if not ok:
                self.handoff_failures += 1
            self.handoff_bytes_total += nbytes
        self._handoff_seconds.observe(seconds)
        if nbytes:
            self._handoff_bytes.inc(nbytes)

    def record_kv_import(self, summary: Dict[str, int]) -> None:
        with self._handoff_lock:
            self.kv_imports_total += 1
            self.kv_imported_pages_total += int(
                summary.get('imported', 0))

    def handoff_stats(self) -> Dict[str, object]:
        with self._handoff_lock:
            return {
                'decode_peers': self.decode_peers(),
                'handoffs': self.handoffs_total,
                'failures': self.handoff_failures,
                'bytes': self.handoff_bytes_total,
                'kv_imports': self.kv_imports_total,
                'kv_imported_pages': self.kv_imported_pages_total,
            }

    # -- live KV-chain migration --------------------------------------------
    #: migrated-in affinity keys retained for controller pinning
    _MIGRATED_KEYS_MAX = 1024
    #: how long an evacuation hint stays actionable
    _EVAC_HINT_TTL_S = 30.0

    def set_evacuation_hint(self, reason: str,
                            target: Optional[str]) -> None:
        """Remember why the engine is about to evacuate (and where the
        controller wants the chains to go). Read by the HTTP threads
        whose futures resolve with SessionMigratedError; expires after
        a grace-window's worth of seconds so a stale rebalance target
        cannot redirect a later drain."""
        with self._migration_lock:
            self._evac_hint = {'reason': str(reason or 'drain'),
                               'target': target or None,
                               'expires': time.monotonic() +
                               self._EVAC_HINT_TTL_S}

    def evacuation_hint(self) -> Tuple[str, Optional[str]]:
        """(reason, target) of the live evacuation hint; defaults to
        ('drain', None) — ring-chosen target — when none is set."""
        with self._migration_lock:
            hint = self._evac_hint
            if hint and time.monotonic() < float(hint['expires']):
                return str(hint['reason']), hint['target']  # type: ignore[return-value]
        return 'drain', None

    def record_evacuation(self, summary: Dict[str, int]) -> None:
        """Account one engine evacuate_chains() result."""
        n_sessions = int(summary.get('evacuated', 0)) + \
            int(summary.get('queued', 0))
        n_chains = int(summary.get('chains', 0))
        with self._migration_lock:
            self.sessions_evacuated_total += n_sessions
            self.chains_evacuated_total += n_chains
        if n_chains:
            self._chains_evacuated.inc(n_chains)

    def record_migration(self, reason: str, seconds: float,
                         ok: bool) -> None:
        """Account one out-migration attempt (chain POST + tail
        proxy). Failed ships count under their own reason AND bump
        migration_failures; the session then finishes locally and the
        fallback is recorded separately as 'local_fallback'."""
        from skypilot_tpu.observability import catalog as _obs
        with self._migration_lock:
            self.migrations_by_reason[reason] = \
                self.migrations_by_reason.get(reason, 0) + 1
            if not ok:
                self.migration_failures += 1
        if ok:
            _obs.counter('skypilot_serving_migrations_total').labels(
                reason=reason).inc()
        self._migration_seconds.observe(seconds)

    def record_migrated_in(self, affinity_key: Optional[str],
                           tokens_recomputed: int) -> None:
        """Account one migrated-in session on the receiving side: the
        re-prefill cost (committed tokens not covered by imported
        pages) and the session's affinity key, kept in a bounded ring
        /stats exposes for LB pinning."""
        with self._migration_lock:
            self.migrations_in_total += 1
            self.tokens_recomputed_total += int(tokens_recomputed)
            if affinity_key:
                self._migrated_in_keys.pop(affinity_key, None)
                self._migrated_in_keys[affinity_key] = None
                while len(self._migrated_in_keys) > \
                        self._MIGRATED_KEYS_MAX:
                    self._migrated_in_keys.popitem(last=False)
        if tokens_recomputed:
            self._tokens_recomputed.inc(int(tokens_recomputed))

    def migration_stats(self) -> Dict[str, object]:
        with self._migration_lock:
            return {
                'migrations': dict(self.migrations_by_reason),
                'failures': self.migration_failures,
                'sessions_evacuated': self.sessions_evacuated_total,
                'chains_evacuated': self.chains_evacuated_total,
                'migrations_in': self.migrations_in_total,
                'tokens_recomputed': self.tokens_recomputed_total,
                'migrated_in_keys': list(self._migrated_in_keys),
            }

    # -- model / adapter resolution -----------------------------------------
    def resolve_model(self, model_field) -> Optional[str]:
        """Map a request's `model` field to an adapter name (None =
        the base model). The OpenAI 404 contract is honored even with
        no adapters configured: an unknown model raises
        AdapterNotFoundError instead of being silently served by the
        base model (the pre-LoRA behavior)."""
        if model_field is None or model_field == '':
            return None
        name = str(model_field)
        if name in (self.model_name, 'base', 'default'):
            return None
        if self.adapters is not None and self.adapters.exists(name):
            return name
        from skypilot_tpu.robustness.errors import AdapterNotFoundError
        known = ([self.model_name] +
                 (self.adapters.inventory()
                  if self.adapters is not None else []))
        raise AdapterNotFoundError(
            f'model {name!r} does not exist (known models: {known})')

    def engine_for(self, adapter: Optional[str] = None):
        """Engine that can run this request: the main engine, or —
        for adapter requests in one-shot mode — the lazy stream
        engine (the one-shot jit buckets have no per-slot LoRA
        path). None = use the one-shot path."""
        if self.engine is not None:
            return self.engine
        if adapter is not None:
            return self.stream_engine()
        return None

    # -- tokenizer ----------------------------------------------------------
    def get_tokenizer(self):
        with self._tok_lock:
            if 'tok' not in self._tok_holder:
                if self.tokenizer_dir is None:
                    raise NoTokenizerError(
                        'no tokenizer available: text endpoints need '
                        'a --hf checkpoint with tokenizer files; use '
                        '/generate with token ids instead')
                from skypilot_tpu.models.hf_import import load_tokenizer
                self._tok_holder['tok'] = load_tokenizer(
                    self.tokenizer_dir)
            return self._tok_holder['tok']

    # -- one-shot path ------------------------------------------------------
    def get_fn(self, batch: int, temperature: float, total: int = 0):
        """One jitted fn per (batch, temperature, total-length) bucket.
        `total` defaults to the engine's full capacity; text endpoints
        pass a smaller bucket so a 4-token completion does not pay for
        a full-buffer decode scan."""
        from skypilot_tpu.models import generate as gen
        if total <= 0:
            total = self.limit_for(temperature)
        key = (batch, temperature, total)
        with self._lock:
            if key not in self._fns:
                if self.speculative > 0 and temperature == 0.0:
                    self._fns[key] = gen.make_speculative_generate_fn(
                        self.model, total, draft_k=self.speculative)
                else:
                    self._fns[key] = gen.make_generate_fn(
                        self.model, total, temperature=temperature)
            return self._fns[key]

    def split_rng(self):
        import jax
        with self._lock:
            self._rng, sub = jax.random.split(self._rng)
        return sub

    def _score_fn(self, bucket: int):
        """Jitted full-sequence log-softmax over a padded bucket
        (teacher-forced scoring — the /v1/completions logprobs/echo
        contract eval harnesses drive)."""
        import jax
        import jax.numpy as jnp
        key = ('score', bucket)
        with self._lock:
            if key not in self._fns:
                model = self.model

                @jax.jit
                def score(params, tokens):
                    logits = model.apply({'params': params}, tokens)
                    return jax.nn.log_softmax(
                        logits.astype(jnp.float32), axis=-1)

                self._fns[key] = score
            return self._fns[key]

    def score_logprobs(self, ids: List[int]):
        """log P(token_i | tokens_<i) for the whole row: returns a
        [len(ids), vocab] numpy array of log-probs (row i scores
        position i+1's candidates)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        bucket = 8
        while bucket < len(ids):
            bucket *= 2
        bucket = min(bucket, self.max_total_len)
        fn = self._score_fn(bucket)
        padded = list(ids) + [0] * (bucket - len(ids))
        lp = fn(self.params, jnp.asarray([padded], jnp.int32))
        return np.asarray(jax.device_get(lp))[0, :len(ids)]

    def one_shot_rows(self, rows: List[List[int]], max_new: int,
                      temperature: float) -> List[List[int]]:
        """Run ragged rows through power-of-two one-shot buckets and
        return each row trimmed to prompt + max_new. Rows sharing a
        bucket could batch; they arrive per-request here, so each runs
        alone (the continuous engine is the batching mode)."""
        import jax
        import jax.numpy as jnp
        limit = self.limit_for(temperature)
        out_rows = []
        for ids in rows:
            want = len(ids) + max_new
            bucket = 8
            while bucket < want:
                bucket *= 2
            bucket = min(bucket, limit)
            fn = self.get_fn(1, temperature, bucket)
            out = fn(self.params, jnp.asarray([ids], jnp.int32),
                     self.split_rng())
            out_rows.append(
                jax.device_get(out)[0][:min(want, bucket)].tolist())
        return out_rows

    # -- streaming path -----------------------------------------------------
    def stream_engine(self):
        """The engine that backs streaming requests: the main engine
        in continuous mode; else a small lazily-built one (shares
        params — HBM cost is its slot KV cache only)."""
        if self.engine is not None:
            return self.engine
        with self._stream_engine_lock:
            if self._stream_engine is None:
                from skypilot_tpu.models.batching import \
                    ContinuousBatchingEngine
                self._stream_engine = ContinuousBatchingEngine(
                    self.model, self.params,
                    num_slots=self._stream_slots,
                    max_total_len=self.engine_total,
                    speculative_k=self.speculative,
                    prefill_chunk=self._prefill_chunk,
                    prefill_budget=self._prefill_budget,
                    pipeline_decode=(None if self.speculative
                                     else self._pipeline_decode),
                    max_queue_requests=self._max_queue_requests,
                    max_queue_tokens=self._max_queue_tokens,
                    adapter_store=self.adapters,
                    mesh=self.mesh)
            return self._stream_engine

    def deadline_for(self, req: dict) -> float:
        """Effective per-request deadline, seconds: the request's own
        `timeout` field clamped into (0, --request-timeout]."""
        try:
            t = float(req.get('timeout', self.request_timeout))
        except (TypeError, ValueError) as e:
            raise ValueError(f'invalid timeout field: {e}') from e
        if t <= 0:
            raise ValueError(f'timeout must be > 0, got {t}')
        return min(t, self.request_timeout)

    def submit_stream(self, ids: List[int], max_new: int,
                      temperature: float, top_k: int = 0,
                      top_p: float = 1.0,
                      stop_token_ids: Optional[List[int]] = None,
                      deadline_s: Optional[float] = None,
                      adapter: Optional[str] = None,
                      trace_ctx: Optional[object] = None
                      ) -> StreamHandle:
        eng = self.stream_engine()
        # Queue must exist before submit; commit-time ITL recording
        # rides the same callback.
        handle = StreamHandle(metrics=self.metrics)
        handle.future = eng.submit(
            ids, max_new_tokens=max_new, temperature=temperature,
            top_k=top_k, top_p=top_p, stop_token_ids=stop_token_ids,
            on_token=handle.on_token,
            deadline_s=(self.request_timeout if deadline_s is None
                        else deadline_s),
            adapter=adapter, trace_ctx=trace_ctx)
        return handle

    def live_engines(self) -> List[object]:
        """Engines constructed so far (main and/or lazy stream engine)
        — the scrape handlers refresh each one's gauges."""
        return [e for e in (self.engine, self._stream_engine)
                if e is not None]

    def cancel_streams(self, handles: List[StreamHandle]) -> None:
        """Abandon streamed requests whose consumer disconnected: the
        engine frees their slots instead of generating unread tokens.
        No-op for handles that already completed."""
        futs = [h.future for h in handles
                if h.future is not None and not h.future.done()]
        if not futs:
            return
        eng = self.engine if self.engine is not None \
            else self._stream_engine
        if eng is not None:
            eng.cancel(futs)

    def stop(self) -> None:
        if self.engine is not None:
            self.engine.stop()
        if self._stream_engine is not None:
            self._stream_engine.stop()


def _init_params(model):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    return nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])


def _init_params_on_host(model):
    """Seeded random weights as a HOST tree (f32, the CPU device)."""
    import jax
    with jax.default_device(jax.devices('cpu')[0]):
        return _init_params(model)


def _init_params_placed(model, mesh, dtype):
    """Seeded random weights created where they are served: under one
    jit whose outputs carry the serving shardings (the model's logical
    axes over `mesh`; a single device without one) and the serving
    dtype, so neither the f32 tree nor an unsharded copy of any leaf
    is ever resident. The threefry RNG is partitionable: each chip
    draws only its own shard."""
    import jax
    shardings = None
    if mesh is not None:
        from skypilot_tpu.parallel.serving import serving_param_shardings
        shardings = serving_param_shardings(model, mesh)
    return jax.jit(
        lambda: jax.tree.map(lambda x: x.astype(dtype),
                             _init_params(model)),
        out_shardings=shardings)()


def build_runtime(args) -> InferenceRuntime:
    """Construct the runtime from serve_lm CLI args: load the model
    (registry or HF checkpoint), place params (TP-sharded over the
    mesh or single-device, bf16 by default), restore a checkpoint if
    given, and build the continuous engine when enabled."""
    import jax
    if args.cpu:
        jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp

    from skypilot_tpu.recipes.train_lm import _build_model
    from skypilot_tpu.utils import compile_cache
    print(f'compile cache: {compile_cache.configure()}', flush=True)

    # The dtype weights are SERVED at, for both sources (--hf and the
    # registry's seeded random weights): bf16 unless --param-dtype
    # f32. Compute runs in bf16 either way; f32 storage only doubles
    # every decode step's weight traffic and the HBM the weights take.
    import ml_dtypes
    import numpy as _np
    param_dtype = getattr(args, 'param_dtype', 'bf16') or 'bf16'
    serve_cast = (ml_dtypes.bfloat16 if param_dtype == 'bf16'
                  else _np.float32)
    tokenizer_dir = None
    hf_params = None
    if args.hf:
        from skypilot_tpu.models import hf_import
        model, hf_params = hf_import.load_hf_checkpoint(
            args.hf, max_seq_len=args.max_total_len)
        # Raw f32 numpy here; the cast (bf16 via ml_dtypes) happens
        # PER LEAF at placement time below — host transient is one
        # leaf, device footprint is the bf16 shards.
        vocab_size = model.config.vocab_size
        print(f'loaded HF checkpoint from {args.hf} '
              f'({type(model).__name__}, vocab={vocab_size})',
              flush=True)
        if any(os.path.exists(os.path.join(args.hf, f))
               for f in ('tokenizer.json', 'tokenizer_config.json',
                         'tokenizer.model')):
            tokenizer_dir = args.hf
    else:
        model, vocab_size, _ = _build_model(args.model,
                                            args.max_total_len,
                                            remat=False)

    # A model whose sequences keep recurrent state by slot beside
    # their pages (ops/paged_attention.SlotArray: state-space layers)
    # refuses here, by flag, what assumes that a sequence is its pages;
    # the engine's constructor refuses the same by name.
    layout = (model.config.page_layout()
              if hasattr(model.config, 'page_layout') else None)
    if layout is not None and layout.slot_arrays:
        asked = {
            'the one-shot engine (give --continuous-batching)':
                not args.continuous_batching,
            '--kv-dtype int8': (getattr(args, 'kv_dtype', 'bf16')
                                or 'bf16') == 'int8',
            '--tensor': int(getattr(args, 'tensor', 1) or 1) > 1,
            '--stages': int(getattr(args, 'stages', 1) or 1) > 1,
            '--speculative': args.speculative > 0,
            '--decode-chunk': getattr(args, 'decode_chunk', 1) > 1,
            '--kv-spill-bytes / --kv-cold-dir': bool(
                getattr(args, 'kv_spill_bytes', 0)
                or getattr(args, 'kv_cold_dir', None)),
            '--role / --decode-peers (handoff ships pages)': bool(
                getattr(args, 'role', '')
                or getattr(args, 'decode_peers', None)),
        }
        wrong = [flag for flag, on in asked.items() if on]
        if wrong:
            raise SystemExit(
                f'{type(model.config).__name__} keeps recurrent state '
                f'by slot beside its pages '
                f'({", ".join(a.name for a in layout.slot_arrays)}): it '
                f'does not serve with {", ".join(wrong)} yet (docs/'
                f'guides.md "State by slot"; ROADMAP R-M5)')

    # Quantized serving knobs (inference/quant.py): KV page storage
    # format + pool sizing in BYTES (so bf16/int8 A/B runs spend the
    # same HBM — int8 buys ~2x the pages), and int8 projection
    # weights below.
    from skypilot_tpu.inference import quant as quant_lib
    kv_dtype = getattr(args, 'kv_dtype', 'bf16') or 'bf16'
    weight_dtype = getattr(args, 'weight_dtype', 'bf16') or 'bf16'
    kv_pool_bytes = int(getattr(args, 'kv_pool_bytes', 0) or 0)
    if kv_dtype != 'bf16' or kv_pool_bytes:
        cfg = model.config
        if getattr(cfg, 'kv_dtype', None) is None or \
                not hasattr(cfg, 'page_layout'):
            raise SystemExit(
                f'--kv-dtype/--kv-pool-bytes need a model config that '
                f'gives a page layout and a kv_dtype field (the Llama '
                f'and DeepSeek families); {type(cfg).__name__} has none')
        if kv_dtype == 'int8' and cfg.page_layout().kind != 'kv':
            raise SystemExit(
                f'--kv-dtype int8 stores K/V pages; '
                f'{type(cfg).__name__} keeps '
                f'{cfg.page_layout().kind!r} pages, which have no '
                f'int8 form yet')
        if kv_dtype == 'int8' and not args.continuous_batching:
            raise SystemExit(
                '--kv-dtype int8 requires --continuous-batching: the '
                'one-shot engine decodes through the dense per-slot '
                'cache, which has no scale storage')
        import dataclasses
        # --kv-pool-bytes is PER-CHIP HBM: under --tensor the pool's
        # kv-heads axis shards (parallel/serving.py GQA remainder
        # rule), one page costs 1/shard_ways the value bytes per
        # chip, and the same per-chip budget buys ~shard_ways x the
        # pages — an N-chip mesh holds ~N x the decode capacity at
        # fixed per-chip memory.
        from skypilot_tpu.parallel.serving import kv_shard_ways
        shard_ways = kv_shard_ways(
            int(getattr(cfg, 'num_kv_heads', 0) or 0),
            int(getattr(args, 'tensor', 1) or 1))
        # Under --stages each stage's pool stores only its own
        # [lo, hi) layer range, so a page costs ~1/S the bytes per
        # chip ON TOP of the tensor split — the same per-chip budget
        # buys ~S*shard_ways x the pages.
        stages = int(getattr(args, 'stages', 1) or 1)
        pages = (quant_lib.pool_pages_for_bytes(cfg, kv_dtype,
                                                kv_pool_bytes,
                                                shard_ways,
                                                stages=stages)
                 if kv_pool_bytes else cfg.kv_total_pages)
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype,
                                  kv_total_pages=pages)
        model = type(model)(cfg)
        sharded = (f', kv heads sharded {shard_ways}-way'
                   if shard_ways > 1 else '')
        staged = (f', split over {stages} stages' if stages > 1
                  else '')
        print(f'kv cache: dtype={kv_dtype} pages={pages} '
              f'({quant_lib.kv_page_bytes(cfg, kv_dtype, shard_ways, stages=stages)} '
              f'bytes/page/chip across layers{sharded}{staged})',
              flush=True)

    # Speculative decoding writes its verify chunk up to K tokens past
    # the last kept one; fail fast / clamp at STARTUP instead of
    # erroring inside every request handler.
    spec_total = args.max_total_len
    if args.speculative > 0:
        spec_total = min(args.max_total_len,
                         model.config.max_seq_len - args.speculative)
        if spec_total <= 1:
            raise SystemExit(
                f'--speculative {args.speculative} needs headroom in '
                f'the model context: max_seq_len='
                f'{model.config.max_seq_len} leaves no room for the '
                f'verify chunk. Use a smaller K or a longer-context '
                f'model.')
        if spec_total < args.max_total_len:
            print(f'speculative decoding: clamping max_total_len '
                  f'{args.max_total_len} -> {spec_total} (verify chunk '
                  f'needs K={args.speculative} tokens of headroom '
                  f'below max_seq_len={model.config.max_seq_len})',
                  flush=True)

    # The serving mesh first: registry weights are created ON it.
    mesh = None
    num_stages = int(getattr(args, 'stages', 1) or 1)
    if num_stages > 1:
        if weight_dtype == 'int8':
            raise SystemExit(
                '--stages does not compose with --weight-dtype int8 '
                '(the quantized wrapper has no per-stage split)')
        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.make_mesh(
            mesh_lib.MeshConfig(stage=num_stages, tensor=args.tensor),
            devices=jax.devices()[:num_stages * args.tensor])
    elif args.tensor > 1:
        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.make_mesh(
            mesh_lib.MeshConfig(tensor=args.tensor),
            devices=jax.devices()[:args.tensor])
    if weight_dtype not in ('bf16', 'int8'):
        raise SystemExit(f'unsupported --weight-dtype {weight_dtype}')

    placed = False
    if hf_params is not None:
        params = hf_params
    elif weight_dtype == 'int8' or num_stages > 1:
        # Host-side consumers below (per-channel quantization, the
        # per-stage split) take a host tree, like --hf hands them.
        params = _init_params_on_host(model)
    else:
        # Already sharded, already in the serving dtype: the whole
        # tree never exists on one chip (llama3-8b is 32 GB in f32
        # against 16 GB of HBM).
        params = _init_params_placed(model, mesh, serve_cast)
        placed = True
    # int8 projection weights: quantize HOST-SIDE from the f32/bf16
    # tree, then wrap the model so every jitted serving fn
    # dequantizes on read (inference/quant.py).
    if weight_dtype == 'int8':
        if args.ckpt_dir:
            raise SystemExit(
                '--weight-dtype int8 does not compose with '
                '--ckpt-dir (the restore template predates '
                'quantization); restore bf16 or convert first')
        qparams = quant_lib.quantize_params(params)
        if not quant_lib.is_quantized(qparams):
            raise SystemExit(
                f'--weight-dtype int8 found no quantizable '
                f'projection kernels ({quant_lib.WEIGHT_TARGETS}) in '
                f'this model; the Llama family is supported')
        params = qparams
        model = quant_lib.QuantizedModel(model)
        print('weights: int8 per-output-channel projections '
              '(dequant-on-read)', flush=True)
    # ONE placement block for every host tree: TP-shard over the mesh
    # (per-leaf cast, shard-only transfers), stage×tensor split, or
    # single-device.
    if num_stages > 1:
        from skypilot_tpu.parallel.serving import build_staged_serving
        # Place per stage HERE (per-leaf cast, shard-only transfers
        # onto each stage's tensor submesh) and hand the engine the
        # re-merged tree: stage key sets are disjoint top-level
        # partitions, so the engine's own build_staged_serving split
        # re-places each already-resident leaf as a no-op.
        _, stage_params, _, _ = build_staged_serving(
            model, params, mesh, dtype=serve_cast)
        params = {}
        for sp in stage_params:
            params.update(sp)
        print(f'pipeline serving: {num_stages} stages x '
              f'{args.tensor}-way tensor over '
              f'{num_stages * args.tensor} devices', flush=True)
    elif args.tensor > 1:
        if weight_dtype == 'int8':
            params = quant_lib.shard_quantized_for_serving(
                model, params, mesh, dtype=serve_cast)
        elif not placed:
            from skypilot_tpu.parallel.serving import \
                shard_params_for_serving
            params = shard_params_for_serving(model, params, mesh,
                                              dtype=serve_cast)
        print(f'tensor-parallel serving over {args.tensor} devices: '
              f'{mesh_lib.mesh_summary(mesh)}', flush=True)
    elif weight_dtype == 'int8':
        # Quantized leaves keep their int8/f32 dtypes; serve_cast
        # applies to the surviving dense leaves (embeddings, norms,
        # head) exactly as the bf16 path does.
        def _place(x):
            x = _np.asarray(x)
            if x.dtype == _np.float32 and x.ndim > 1:
                x = x.astype(serve_cast)
            return jnp.asarray(x)

        params = jax.tree.map(_place, params)
    elif not placed:
        params = jax.tree.map(
            lambda x: jnp.asarray(_np.asarray(x).astype(serve_cast)),
            params)
    if args.ckpt_dir:
        from skypilot_tpu.parallel.checkpoints import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir)
        if mgr.latest_step() is not None:
            from skypilot_tpu.parallel.train import TrainState
            import optax
            template = TrainState.create(params, optax.sgd(1e-3))
            params = mgr.restore(template).params
            print(f'loaded checkpoint step {mgr.latest_step()}',
                  flush=True)

    # Multi-LoRA adapter registry (serve_lm --adapter-dir): scanned
    # at startup, hot-loaded on demand; every engine in the process
    # shares the one device store.
    adapters = None
    adapter_dir = getattr(args, 'adapter_dir', None)
    if adapter_dir:
        from skypilot_tpu.inference.adapters import AdapterRegistry
        adapters = AdapterRegistry(
            adapter_dir, model,
            # Staged engines keep the adapter stacks UNCOMMITTED
            # (host-backed): each per-stage jitted fn pulls them onto
            # its own submesh, which a mesh-committed stack can't do.
            max_adapters=getattr(args, 'max_adapters', 8),
            max_rank=getattr(args, 'max_lora_rank', 0),
            mesh=None if num_stages > 1 else mesh)
        inv = adapters.inventory()
        print(f'adapter registry: {len(inv)} adapters in '
              f'{adapter_dir} (max {adapters.max_adapters} '
              f'device-resident): {inv}', flush=True)

    engine_total = (spec_total if args.speculative > 0
                    else args.max_total_len)
    engine = None
    prefill_chunk = getattr(args, 'prefill_chunk', 0)
    prefill_budget = getattr(args, 'prefill_budget', 0)
    pipeline_decode = (False if getattr(args, 'no_pipeline_decode',
                                        False) else None)
    request_timeout = getattr(args, 'request_timeout', 600.0)
    max_queue_requests = getattr(args, 'max_queue_requests', 0)
    max_queue_tokens = getattr(args, 'max_queue_tokens', 0)
    # Disaggregation + tiered-cache knobs. Both need the paged slot
    # engine: the spill tier stores prefix-cache pages, and a prefill
    # role without an exportable prefix cache has nothing to hand off.
    role = getattr(args, 'role', '') or ''
    kv_spill_bytes = int(getattr(args, 'kv_spill_bytes', 0) or 0)
    kv_cold_dir = getattr(args, 'kv_cold_dir', None)
    decode_peers = [p for p in
                    (getattr(args, 'decode_peers', None) or ''
                     ).split(',') if p]
    if (kv_spill_bytes or kv_cold_dir) and \
            not args.continuous_batching:
        raise SystemExit(
            '--kv-spill-bytes/--kv-cold-dir need '
            '--continuous-batching (the spill tier stores evicted '
            'prefix-cache pages of the paged slot engine)')
    if role == 'prefill' and not args.continuous_batching:
        raise SystemExit(
            '--role prefill needs --continuous-batching (the handoff '
            'exports KV page chains from the slot engine\'s prefix '
            'cache)')
    if args.continuous_batching:
        from skypilot_tpu.models.batching import ContinuousBatchingEngine
        decode_chunk = getattr(args, 'decode_chunk', 1)
        if decode_chunk > 1:
            # The chunk writes past a finishing request; clamp like
            # the speculative engine does (fail fast at startup) and
            # ADVERTISE the clamped capacity (limit_for must match
            # what engine.submit accepts).
            clamped = min(engine_total,
                          model.config.max_seq_len - decode_chunk)
            if clamped < engine_total:
                print(f'decode chunking: clamping max_total_len '
                      f'{engine_total} -> {clamped} (chunk writes '
                      f'need N={decode_chunk} tokens of headroom '
                      f'below max_seq_len='
                      f'{model.config.max_seq_len})', flush=True)
            engine_total = clamped
        engine = ContinuousBatchingEngine(
            model, params, num_slots=args.num_slots,
            max_total_len=engine_total,
            prefix_caching=not args.no_prefix_caching,
            speculative_k=args.speculative,
            decode_chunk=decode_chunk,
            prefill_chunk=prefill_chunk,
            prefill_budget=prefill_budget,
            # Auto (None) keeps pipelining off for spec/decode-chunk
            # engines; --no-pipeline-decode forces it off everywhere.
            pipeline_decode=pipeline_decode,
            max_queue_requests=max_queue_requests,
            max_queue_tokens=max_queue_tokens,
            adapter_store=adapters,
            kv_spill_bytes=kv_spill_bytes,
            kv_cold_dir=kv_cold_dir,
            mesh=mesh)

    rt = InferenceRuntime(
        model=model, params=params, vocab_size=vocab_size,
        model_name=(f'hf:{os.path.basename(args.hf)}'
                    if args.hf else args.model),
        max_total_len=args.max_total_len, spec_total=spec_total,
        speculative=args.speculative, engine=engine,
        engine_total=engine_total if engine is not None else None,
        tokenizer_dir=tokenizer_dir,
        prefill_chunk=prefill_chunk, prefill_budget=prefill_budget,
        pipeline_decode=pipeline_decode,
        request_timeout=request_timeout,
        max_queue_requests=max_queue_requests,
        max_queue_tokens=max_queue_tokens,
        adapters=adapters,
        # What the weights ARE, not what a flag defaulted to: int8
        # projections, else the dtype they were placed in.
        kv_dtype=kv_dtype,
        weight_dtype=('int8' if weight_dtype == 'int8'
                      else param_dtype),
        role=role, decode_peers=decode_peers, mesh=mesh,
        stream_final=getattr(args, 'stream_final', 'rows'))
    from skypilot_tpu.observability import catalog as _obs_catalog
    _obs_catalog.gauge('skypilot_serving_weight_bytes').set(
        rt.weight_bytes)
    _obs_catalog.gauge('skypilot_serving_storage_info').labels(
        kv_dtype=kv_dtype, weight_dtype=rt.weight_dtype).set(1)
    # Distributed tracing: head-sample at the configured rate; the
    # process tag makes this node's spans a distinct pid row in the
    # merged Chrome trace.
    trace_sample = float(getattr(args, 'trace_sample', 0.0) or 0.0)
    if trace_sample > 0.0:
        from skypilot_tpu.observability import tracing
        tracing.configure(sample=trace_sample,
                          seed=getattr(args, 'trace_seed', None),
                          process=role or 'replica')
    # Declarative SLO targets: one tracker feeds both the /stats slo
    # section and the skypilot_serving_slo_* gauges, recorded through
    # the ServingMetrics hooks.
    slo_spec = getattr(args, 'slo', None)
    if slo_spec:
        from skypilot_tpu.observability import slo as slo_lib
        rt.slo_tracker = slo_lib.SloTracker(
            slo_lib.parse_slo(slo_spec))
        rt.metrics.slo = rt.slo_tracker
    return rt
