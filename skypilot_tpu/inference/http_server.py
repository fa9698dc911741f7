"""HTTP front of the inference runtime.

JetStream-shaped native endpoints + OpenAI shims:

  GET  /                       readiness + capacity
  GET  /stats                  engine + serving metrics, JSON
                               (rolling-window percentiles)
  POST /debug/profile          one jax.profiler session of this
                               process, {"seconds", "dir"}
  GET  /metrics                Prometheus text exposition of the
                               process registry: engine internals
                               (queue depth, slots, page pool,
                               prefix cache, preemptions) + request
                               path (TTFT/ITL/e2e histograms, token
                               counters) — see docs/guides.md for
                               the metric catalog
  POST /generate               token ids in/out; `stream` = SSE of
                               {"index", "token"} events
  POST /generate_text          text in/out via the --hf tokenizer;
                               `stream` = SSE of {"index", "delta"}
  POST /v1/completions         OpenAI completions (+SSE, n>1)
  POST /v1/chat/completions    OpenAI chat (+SSE, n>1)

Graceful drain on SIGTERM (rolling updates / replica replacement):
stop accepting, wait out in-flight requests up to --drain-grace
seconds, exit 0 via os._exit (skipping the XLA C++ teardown, which is
crash-prone under signal-interleaved shutdown).
"""
from __future__ import annotations

import json
import os
import signal
import threading
import time
import uuid as uuid_lib
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List

from skypilot_tpu.inference import openai_compat as oai
from skypilot_tpu.inference import sse
from skypilot_tpu.inference.runtime import (InferenceRuntime,
                                            iter_interleaved)
from skypilot_tpu.observability import REGISTRY
from skypilot_tpu.observability import catalog as obs_catalog
from skypilot_tpu.observability import tracing
from skypilot_tpu.ops import pallas_paged as _pallas_paged
from skypilot_tpu.parallel import mesh as _mesh_lib
from skypilot_tpu.robustness import faults
from skypilot_tpu.robustness import train_guard
from skypilot_tpu.robustness.errors import (AdapterLoadError,
                                            AdapterNotFoundError,
                                            DeadlineExceededError,
                                            EngineDeadError,
                                            QueueSaturatedError,
                                            SessionMigratedError)


#: This process's replica instance identity, echoed in `GET /stats`.
#: The replica plane's manager journals the UUID it handed the
#: process at spawn (STPU_REPLICA_INSTANCE_UUID) and, on controller
#: restart, adopts a pid/port only if the echo matches — a recycled
#: pid or a stranger's server on the old port fails the check.
#: Standalone servers mint their own (adoption simply never matches
#: a replica the journal does not know).
INSTANCE_UUID = (os.environ.get('STPU_REPLICA_INSTANCE_UUID') or
                 uuid_lib.uuid4().hex)


def classify_error(e: Exception):
    """(http_status, retry_after_s) for a request-path exception: the
    robustness taxonomy (429 shed / 504 deadline / 503 engine dead or
    adapter load failure / 404 unknown model) ahead of the 400
    catch-all."""
    if isinstance(e, QueueSaturatedError):
        return 429, e.retry_after_s
    if isinstance(e, DeadlineExceededError):
        return 504, None
    if isinstance(e, SessionMigratedError):
        # Resume failed end to end (peer ship AND local replay): 503
        # is retryable — the LB resubmits on another replica instead
        # of surfacing the evacuation to the client.
        return 503, 0.5
    if isinstance(e, (EngineDeadError, AdapterLoadError)):
        return 503, None
    if isinstance(e, AdapterNotFoundError):
        return 404, None
    return 400, None


def _submit_all(engine, rows: List[List[int]], **kw):
    """Submit one request's rows; if submission k is shed (bounded
    queue filled mid-batch), cancel the k-1 already-submitted rows —
    they would decode for a client that is getting a 429."""
    futs = []
    try:
        for row in rows:
            futs.append(engine.submit(row, **kw))
    except Exception:
        if futs:
            engine.cancel(futs)
        raise
    return futs


def make_server(rt: InferenceRuntime,
                port: int) -> ThreadingHTTPServer:
    """Build the (not yet serving) HTTP server for `rt`. Split from
    `serve()` so tests can run it on an ephemeral port from a thread
    (serve() additionally installs the SIGTERM drain, which only
    works on the main thread). The in-flight POST count rides on the
    returned server as `.inflight`/`.inflight_lock`."""

    # Live POSTs (graceful drain waits on this, covering the window
    # between accept and engine submit and the one-shot engine).
    _inflight = {'n': 0}
    _inflight_lock = threading.Lock()
    # Rolling-update drain: set before the accept loop stops, so
    # /readyz flips to 503 while in-flight requests finish (k8s
    # readiness probes pull the replica out of rotation first).
    _draining = threading.Event()
    # One profiler session at a time (POST /debug/profile).
    _profiling = threading.Lock()

    class Handler(BaseHTTPRequestHandler):
        # (seconds from handler entry to the engine submit's return,
        # stream handles) of a streamed request whose first token has
        # not reached the socket yet.
        _ttft_pending = None

        def log_message(self, *a):  # quiet
            pass

        # -- writer surface (also used by openai_compat) ------------
        def _json(self, obj, code=200, headers=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header('Content-Type', 'application/json')
            self.send_header('Content-Length', str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def send_json(self, obj, code=200):
            self._json(obj, code)

        def sse_start(self, handles=()):
            # A streamed generation passes its stream handles: the
            # engine's submit() has just returned for every row, which
            # ends the first half of the HTTP layer's share of the
            # first token (the second ends in sse_send).
            self._ttft_pending = (
                (time.monotonic() - self._t_enter, handles)
                if handles else None)
            self.send_response(200)
            self.send_header('Content-Type', 'text/event-stream')
            self.send_header('Cache-Control', 'no-cache')
            self.send_header('Connection', 'close')
            self.end_headers()
            self._sse_open = True

        def sse_send(self, obj):
            self.wfile.write(b'data: ' + json.dumps(obj).encode() +
                             b'\n\n')
            self.wfile.flush()
            if self._ttft_pending is not None:
                self._observe_ttft_overhead(*self._ttft_pending)

        def _observe_ttft_overhead(self, submit_s, handles):
            """Once a request, at the first frame flushed after a
            row's first token was committed: handler entry to the
            submit's return, plus that commit to this flush."""
            firsts = [h.first_token_t for h in handles
                      if h.first_token_t is not None]
            if firsts:
                self._ttft_pending = None
                rt.metrics.prom.http_ttft_overhead_seconds.observe(
                    submit_s + time.monotonic() - min(firsts))

        def sse_done(self):
            self.wfile.write(b'data: [DONE]\n\n')
            self.wfile.flush()

        # -- GET ----------------------------------------------------
        def do_GET(self):  # noqa: N802
            if self.path == '/healthz':
                # Liveness: the process is up and serving HTTP. Never
                # reflects load or drains — k8s restarts on liveness
                # failure, and restarting a merely-busy replica is
                # how overload cascades start.
                self._json({'status': 'alive'})
                return
            if self.path == '/readyz':
                self._readyz()
                return
            if self.path in ('/stats', '/v1/stats'):
                self._stats()
                return
            if self.path in ('/metrics', '/v1/metrics'):
                self._prometheus_metrics()
                return
            if self.path.startswith('/debug/trace/'):
                self._debug_trace(
                    self.path[len('/debug/trace/'):].strip('/'))
                return
            if self.path == '/debug/flight':
                self._debug_flight()
                return
            if self.path == '/debug/pool_collectives':
                self._debug_pool_collectives()
                return
            if self.path == '/v1/models':
                # OpenAI client bootstrap: most SDKs list models
                # before first use. Adapters are models: the `model`
                # field on /v1/* selects one (base name = base model).
                names = [rt.model_name]
                if rt.adapters is not None:
                    names += rt.adapters.inventory()
                self._json({'object': 'list',
                            'data': [{'id': name,
                                      'object': 'model',
                                      'owned_by': 'skypilot-tpu'}
                                     for name in names]})
                return
            # Advertise the MINIMUM capacity across request classes
            # (speculative clamp, decode-chunk clamp) — clients sizing
            # prompts off this can never be rejected.
            self._json({'status': 'ok',
                        'model': rt.model_name,
                        'vocab_size': rt.vocab_size,
                        'max_total_len': min(rt.limit_for(0.0),
                                             rt.limit_for(1.0))})

        def _readyz(self):
            """Readiness: should this replica receive NEW traffic?
            503 while draining (SIGTERM received), when an engine's
            scheduler thread died, or when the bounded queue is
            saturated — each with the reason, so `kubectl describe`
            (or a curl) says WHY the replica left rotation."""
            reasons = []
            if _draining.is_set():
                reasons.append('draining')
            for eng in rt.live_engines():
                if not eng.healthy():
                    reasons.append('engine dead')
                if eng.saturated():
                    reasons.append('queue saturated')
            self._json({'ready': not reasons, 'reasons': reasons},
                       200 if not reasons else 503)

        def _debug_trace(self, trace_id):
            """Completed spans THIS process recorded for one trace,
            as a Chrome-trace JSON body. `stpu trace` fetches this
            from every fleet process and merges on the shared
            trace_id."""
            body = tracing.get_trace(trace_id)
            if body is None:
                self._json({'error': f'unknown trace {trace_id!r}',
                            'known': tracing.trace_ids()[-16:]}, 404)
                return
            self._json(body)

        def _debug_flight(self):
            """Flight-recorder dump of every live engine: the last N
            scheduler events (admit, chunk dispatch, round commit,
            preemption, eviction, spill, restore, handoff, soft
            error, reset), recorded unconditionally — the post-mortem
            readout when a replica wedges or dies."""
            self._json({
                'instance_uuid': INSTANCE_UUID,
                'pid': os.getpid(),
                'role': rt.role,
                'engines': [eng.flight.dump()
                            for eng in rt.live_engines()],
            })

        def _debug_profile(self):
            """`{"seconds": s, "dir": path}`: one jax.profiler
            session of `s` seconds in THIS process, the one that
            holds the chip, written under `path`: device events and
            the host tracer (which records the scheduler's `engine.*`
            phases on the device events' clock), the Python tracer
            off, since it slows the loop it would observe. Answers
            when the session has stopped; 409 while another runs.
            Load `path` in Perfetto or TensorBoard."""
            try:
                req = self._read_body()
                seconds, out_dir = float(req['seconds']), str(req['dir'])
                if not 0 < seconds <= 120 or not out_dir:
                    raise ValueError('seconds in (0, 120] and a dir')
            except (KeyError, TypeError, ValueError) as e:
                self._json({'error': f'{type(e).__name__}: {e}'}, 400)
                return
            if not _profiling.acquire(blocking=False):
                self._json({'error': 'a profiler session is running'},
                           409)
                return
            try:
                import jax
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(out_dir,
                                         profiler_options=options)
                try:
                    time.sleep(seconds)
                finally:
                    jax.profiler.stop_trace()
            finally:
                _profiling.release()
            self._json({'dir': out_dir, 'seconds': seconds})

        def _debug_pool_collectives(self):
            """The page-pool guards on the programs as compiled HERE
            (docs/guides.md "Sharded serving"): `lines` lists HLO
            collectives of the decode dispatch that move a pool-shaped
            operand — empty means a tensor-sharded pool is never
            gathered; `copies` lists, per program (decode, one prefill
            chunk), HLO copies that produce a pool-shaped array —
            empty means the KV write is in place. Compiles (or reads
            the compile cache), so it is a bring-up check, not a
            scrape target."""
            try:
                lines = copies = None
                if rt.engine is not None:
                    lines = rt.engine.decode_pool_collectives()
                    copies = rt.engine.pool_copy_lines()
            except Exception as e:  # pylint: disable=broad-except
                self._plain_error(e)
                return
            self._json({'mesh_devices': rt.mesh_devices,
                        'stages': rt.stages, 'lines': lines,
                        'copies': copies})

        def _prometheus_metrics(self):
            """Prometheus text exposition of the process registry.
            Snapshot gauges (queue depth, slot occupancy, page pool)
            refresh from live engine state at scrape time; counters
            and histograms tick at their event sites."""
            for eng in rt.live_engines():
                eng.update_metric_gauges()
            body = REGISTRY.render().encode()
            self.send_response(200)
            self.send_header('Content-Type', REGISTRY.CONTENT_TYPE)
            self.send_header('Content-Length', str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _stats(self):
            """Engine observability (the vLLM /metrics idea, JSON):
            slot occupancy, page pool, prefix-cache hit rate,
            speculation quality, and serving latency percentiles
            over the rolling window documented by the `window` key
            (GET /metrics carries the same signals as lifetime
            Prometheus series)."""
            engine = rt.engine
            body = {'serving': rt.metrics.snapshot(),
                    'instance_uuid': INSTANCE_UUID,
                    'pid': os.getpid(),
                    # Disaggregated serving: '' = unified replica.
                    'role': rt.role,
                    # Quantized-serving storage formats + weight
                    # footprint (docs/guides.md "Quantized serving").
                    'storage': {
                        'kv_dtype': rt.kv_dtype,
                        'weight_dtype': rt.weight_dtype,
                        'weight_bytes': rt.weight_bytes,
                        # Mesh-sharded serving (docs/guides.md
                        # "Sharded serving"): devices the engines'
                        # state spans (1 = single device).
                        'mesh_devices': rt.mesh_devices,
                        # Pipeline-parallel serving (--stages): stage
                        # count of the (stage, tensor) mesh (1 = no
                        # stage split; tensor ways = mesh_devices /
                        # stages).
                        'stages': rt.stages,
                        # Fused kernel path (docs/guides.md "Fused
                        # kernel path & roofline"): why the COMPILED
                        # pallas route is unavailable here, or null
                        # when it can run (interpret mode always can).
                        'attention_kernel_unavailable_reason':
                            _pallas_paged.unavailable_reason(),
                    },
                    # Per-device bytes in use / peak, from the process
                    # that holds the chips (nulls off-accelerator).
                    'device_memory': _mesh_lib.device_memory()}
            if rt.role or rt.handoffs_total or rt.kv_imports_total:
                body['handoff'] = rt.handoff_stats()
            mig = rt.migration_stats()
            if mig['sessions_evacuated'] or mig['migrations'] or \
                    mig['migrations_in']:
                # Live migration: out/in counts, recompute cost, and
                # the migrated-in affinity keys the fleet controller
                # pins at the LB so follow-ups land on the warm pages.
                body['migration'] = mig
            if rt.adapters is not None:
                body['adapters'] = rt.adapters.stats()
            if rt.slo_tracker is not None:
                body['slo'] = rt.slo_tracker.snapshot()
            if engine is None:
                body['engine'] = 'simple'
                self._json(body)
                return
            engine.update_metric_gauges()
            body.update({
                'engine': 'continuous',
                'num_slots': engine.num_slots,
                'active_slots': int(engine.active.sum()),
                'queued': engine._queue.qsize() + len(engine._ready),
                'decode_calls': engine.decode_calls,
                'tokens_committed': engine.tokens_committed,
                'tokens_per_call': round(
                    engine.tokens_committed /
                    max(engine.decode_calls, 1), 3),
                'speculative_k': engine.spec_k,
                'preemptions': engine.preemptions,
                # Stall-free scheduler: chunked-prefill + pipelining
                # health (docs/guides.md serving-tuning section).
                'prefill_chunk': engine.prefill_chunk,
                'prefill_token_budget': engine.prefill_budget,
                'pipeline_decode': engine.pipeline_decode,
                'prefill_chunks_run': engine.prefill_chunks_run,
                # Finished prompts by the way their first token took:
                # to the next round on the device, or through the
                # scheduler's blocking fetch.
                'first_tokens_deferred': engine.first_tokens_deferred,
                'first_tokens_synced': engine.first_tokens_synced,
                'prefill_backlog_tokens':
                    engine.prefill_backlog_tokens(),
                # The scheduler loop's phases (docs/guides.md
                # "Scheduler phases"): self seconds and counts per
                # phase; `loop_s` is the loop's wall time, which they
                # partition; `decode_stall_s` is engine.fetch_wait's.
                'decode_stall_s': round(engine.decode_stall_s, 6),
                'phases': engine.phase_stats(),
                'loop_s': round(engine.loop_s, 6),
                'time': time.time(),
                # Every request's intervals on the way to its first
                # token, as bucket counts (the histograms /metrics
                # renders): a reader takes a window's percentile from
                # the growth between two scrapes.
                'latency': {
                    'queue_wait': obs_catalog.latency_stats(
                        engine.metrics.queue_wait_seconds),
                    'admit_to_first_token': obs_catalog.latency_stats(
                        engine.metrics.prefill_seconds),
                    'http_ttft_overhead': obs_catalog.latency_stats(
                        rt.metrics.prom.http_ttft_overhead_seconds),
                },
                # Pipeline-parallel serving (--stages): stage count
                # and the closed-form (S-1)/(M+S-1) fill/drain bubble
                # of the last prefill burst (0.0 when unstaged).
                'pipeline_stages': engine.stages,
                'prefill_bubble_fraction': round(
                    engine._prefill_bubble, 6),
                # Fused kernel path + analytic HBM roofline inputs
                # (ops/pallas_paged.py; serve_bench scores achieved
                # tokens/s against bytes_per_token * HBM peak).
                'attention_impl': engine.attention_impl(),
                # The route of a whole prefill chunk's attention: a
                # chip run that fell back to the XLA walk reads so.
                'chunk_attention_impl': engine.chunk_attention_impl(),
                'attention_bytes_per_token':
                    engine.attention_bytes_per_token(),
                # Robustness plane (docs/guides.md serving-robustness
                # section): shedding, deadlines, crash containment.
                'healthy': engine.healthy(),
                'requests_shed': engine.requests_shed,
                'deadline_exceeded': engine.deadline_exceeded,
                'engine_restarts': engine.engine_restarts,
                # Errors the scheduler contained without a restart
                # (failed requests on a server that stayed up).
                'soft_errors': engine.soft_errors_total,
                # Which cache layout runs, and why ('paged: ...' /
                # 'dense: ...').
                'kv_cache': engine.kv_cache_choice,
                'queued_tokens': engine.queued_tokens(),
                'max_queue_requests': engine.max_queue_requests,
                'max_queue_tokens': engine.max_queue_tokens,
            })
            # A model's own device counters (a routed model's
            # `expert_tokens` / `expert_calls_touched`, each
            # {block: [[decode...], [prefill...]]} over the held
            # experts; `sparse_decode_tokens`): top-level keys.
            for name, blocks in engine.model_counters().items():
                body[name] = (blocks[''] if set(blocks) == {''}
                              else blocks)
            if engine.paged:
                free = int(engine.allocator.free_pages)
                body['page_pool'] = {
                    'total': engine.total_pages,
                    'free': free,
                    'used': engine.total_pages - free,
                    'utilization': round(
                        (engine.total_pages - free) /
                        max(engine.total_pages, 1), 3),
                    'kv_dtype': engine.kv_dtype,
                    'pool_bytes': engine.kv_cache_bytes(),
                    # Per-chip view of the sharded pool: bytes ONE
                    # device holds and how many ways the kv-heads
                    # axis actually split (1 = replicated — single
                    # device or the GQA remainder rule fired).
                    'pool_bytes_per_device':
                        engine.kv_cache_bytes_per_device(),
                    'shard_ways': engine.kv_shard_ways,
                    # What a cached token's row is made of, as the
                    # model's page layout gives it, and its bytes
                    # over all layers.
                    'row_layout': engine.page_layout.describe(
                        engine.model.config.num_layers,
                        1 if engine.kv_dtype == 'int8' else 2),
                }
                if engine.slot_state:
                    # What a sequence keeps by slot beside its pages
                    # (a model with state-space layers).
                    body['state_pool'] = engine.state_pool_stats()
                if engine.stages > 1:
                    # Staged pool split: every stage stores the same
                    # page indices (one shared allocator) but only
                    # its own layer range's bytes.
                    body['page_pool']['stages'] = \
                        engine.stage_pool_stats()
                if engine.prefix_cache is not None:
                    pc = engine.prefix_cache
                    body['prefix_cache'] = {
                        'hits': pc.hits,
                        'misses': pc.misses,
                        'hit_rate': round(
                            pc.hits / max(pc.hits + pc.misses, 1), 3),
                        'evictions': pc.evictions,
                        'resident_unreferenced': len(pc.lru),
                    }
                if engine.spill_tier is not None:
                    # Tiered cache: the host/cold spill tier's own
                    # accounting + the engine-level restore outcome
                    # (docs/guides.md "Disaggregated serving & cache
                    # tiering").
                    spill = engine.spill_tier.stats()
                    spill.update({
                        'restore_lookups': engine.kv_restore_lookups,
                        'restore_hits': engine.kv_restore_hits,
                        'restored_into_pool':
                            engine.kv_restored_pages,
                    })
                    body['kv_spill'] = spill
            self._json(body)

        # -- POST ---------------------------------------------------
        def do_POST(self):  # noqa: N802
            self._t_enter = time.monotonic()
            with _inflight_lock:
                _inflight['n'] += 1
            try:
                self._do_post()
            finally:
                with _inflight_lock:
                    _inflight['n'] -= 1

        def _read_body(self):
            # The KV-handoff paths re-dispatch an embedded request
            # into the normal handlers; the injected body stands in
            # for the (already consumed) socket payload.
            injected = getattr(self, '_injected_body', None)
            if injected is not None:
                self._injected_body = None
                return injected
            length = int(self.headers.get('Content-Length', 0))
            return json.loads(self.rfile.read(length))

        def _route_generation(self, path):
            """Generation-path handler for `path`, or None. Shared by
            the normal POST dispatch and the /kv/import embedded-
            request re-dispatch (the decode side of a handoff)."""
            if path == '/v1/completions':
                return self._openai_completions
            if path == '/v1/chat/completions':
                return self._openai_chat
            if path in ('/generate_text', '/v1/generate_text'):
                return self._generate_text
            if path in ('/generate', '/v1/generate'):
                return self._generate
            return None

        def _do_post(self):
            if faults.point('http.handler') is faults.DROP:
                return  # injected blackhole: client sees a hang/reset
            # Adopt the caller's trace (LB or prefill peer sent the
            # x-skypilot-trace header) or make the head-sampling
            # decision here; unsampled = one float compare, no span.
            ctx = tracing.parse_header(
                self.headers.get(tracing.HEADER))
            if ctx is None:
                ctx = tracing.new_ctx()
            if ctx is None:
                self._trace_ctx = None
                self._dispatch_post()
                return
            with tracing.span('replica.request', ctx,
                              process=rt.role or 'replica',
                              path=self.path) as root:
                # Children (engine spans, handoff spans) parent to
                # this request root, not to the wire parent.
                self._trace_ctx = root.ctx
                self._dispatch_post()

        def _dispatch_post(self):
            if self.path == '/kv/import':
                self._kv_import()
                return
            if self.path == '/kv/peers':
                self._kv_peers()
                return
            if self.path == '/kv/evacuate':
                self._kv_evacuate()
                return
            if self.path == '/kv/migrate':
                self._kv_migrate()
                return
            if self.path == '/debug/profile':
                self._debug_profile()
                return
            handler = self._route_generation(self.path)
            if handler is None:
                self._json({'error': 'POST /generate, /generate_text, '
                                     'or /v1/completions'}, 404)
                return
            if rt.role == 'prefill':
                try:
                    body = self._read_body()
                except (ValueError, OSError):
                    body = None  # malformed: the handler's 400 to give
                if body is not None:
                    if self._maybe_handoff(self.path, body):
                        return
                    self._injected_body = body
            handler()

        # -- disaggregated prefill/decode handoff -------------------
        def _kv_peers(self):
            """Fleet-controller push of the decode pool this prefill
            replica hands off to."""
            try:
                req = self._read_body()
                peers = [str(p) for p in (req.get('decode') or [])]
                rt.set_decode_peers(peers)
                self._json({'decode': peers})
            except Exception as e:  # pylint: disable=broad-except
                self._json({'error': f'{type(e).__name__}: {e}'}, 400)

        def _kv_import(self):
            """Decode side of a handoff: scatter the POSTed page
            chain into the pool + prefix cache and — when the body
            embeds the original request — serve it immediately: the
            admission finds every full prompt page already resident,
            so the request enters decoding with only the sub-page
            prompt tail recomputed (no re-prefill)."""
            import base64
            try:
                req = self._read_body()
                data = base64.b64decode(req['payload'])
                eng = rt.engine if rt.engine is not None \
                    else rt.stream_engine()
                with tracing.span('kv.import',
                                  getattr(self, '_trace_ctx', None),
                                  bytes=len(data)):
                    summary = eng.import_chain(data)
                rt.record_kv_import(summary)
            except Exception as e:  # pylint: disable=broad-except
                self._plain_error(e)
                return
            inner = req.get('request')
            if not inner:
                self._json({'imported': summary})
                return
            inner_path = str(req.get('path') or '/generate')
            handler = self._route_generation(inner_path)
            if handler is None:
                self._json({'error': f'unroutable handoff path '
                                     f'{inner_path!r}'}, 400)
                return
            self.path = inner_path
            self._injected_body = inner
            handler()

        # -- live KV-chain migration --------------------------------
        def _kv_evacuate(self):
            """Controller-initiated evacuation: a scale-down drain
            POSTs {reason: 'drain'} before SIGTERM, a rebalance POSTs
            {reason: 'rebalance', target, max_sessions}. Every
            evacuated session's future resolves with
            SessionMigratedError; the owning HTTP threads ship the
            chains (to `target` when given, else the peer ring picks)
            and proxy the tails. Responds with the evacuation count —
            the migrations themselves complete asynchronously on
            those threads."""
            try:
                req = self._read_body()
            except (ValueError, OSError):
                req = {}
            reason = str(req.get('reason') or 'drain')
            target = req.get('target') or None
            max_sessions = req.get('max_sessions')
            if max_sessions is not None:
                max_sessions = int(max_sessions)
            rt.set_evacuation_hint(reason, target)
            total = {'evacuated': 0, 'chains': 0, 'queued': 0}
            try:
                for eng in rt.live_engines():
                    fn = getattr(eng, 'evacuate_chains', None)
                    if fn is None:
                        continue
                    s = fn(max_sessions=max_sessions, reason=reason)
                    for k in total:
                        total[k] += int(s.get(k, 0))
                rt.record_evacuation(total)
            except Exception as e:  # pylint: disable=broad-except
                self._json({'error': f'{type(e).__name__}: {e}'}, 500)
                return
            self._json(dict(total, reason=reason))

        def _kv_migrate(self):
            """Receiving side of a live migration: import the packed
            committed-token chain (when one shipped), account the
            re-prefill cost and the session's affinity key (the ring
            /stats exposes for LB pinning), then serve the embedded
            continuation request — admission finds the committed full
            pages resident, so only the sub-page tail recomputes and
            greedy decoding continues bit-identically."""
            import base64
            try:
                req = self._read_body()
                inner = req.get('request') or {}
                rows = inner.get('tokens') or []
                row = ([int(t) for t in rows[0]]
                       if rows and isinstance(rows[0], list) else [])
                eng = rt.engine if rt.engine is not None \
                    else rt.stream_engine()
                summary = {'pages': 0, 'imported': 0,
                           'already_cached': 0, 'dropped': 0}
                if req.get('payload'):
                    data = base64.b64decode(req['payload'])
                    with tracing.span('kv.import',
                                      getattr(self, '_trace_ctx',
                                              None),
                                      bytes=len(data)):
                        summary = eng.import_chain(data)
                    rt.record_kv_import(summary)
                page_size = int(getattr(eng, 'page_size', 0) or 0)
                covered = (summary['imported'] +
                           summary['already_cached']) * page_size
                recomputed = max(0, len(row) - covered) if row else 0
                key = None
                if row and getattr(eng, 'paged', False):
                    from skypilot_tpu.inference import affinity
                    key = affinity.token_affinity_key(
                        row, page_size,
                        salt=affinity.adapter_salt(inner.get('model')))
                rt.record_migrated_in(key, recomputed)
            except Exception as e:  # pylint: disable=broad-except
                self._plain_error(e)
                return
            if not inner:
                self._json({'imported': summary})
                return
            inner_path = str(req.get('path') or '/generate')
            handler = self._route_generation(inner_path)
            if handler is None:
                self._json({'error': f'unroutable migration path '
                                     f'{inner_path!r}'}, 400)
                return
            self.path = inner_path
            self._injected_body = inner
            handler()

        def _migrate_record(self, rec, stream):
            """Ship one evacuated session to a peer: POST the chain +
            continuation request to /kv/migrate and return the open
            upstream response (the caller proxies body or SSE tail).
            None on ANY failure — injected kv.migrate fault, no peer,
            peer refused — and the caller resumes locally on the
            promoted warm pages."""
            import base64

            import requests as requests_lib
            reason = str(rec.get('reason') or 'drain')
            _hint_reason, target = rt.evacuation_hint()
            t0 = time.monotonic()
            try:
                if faults.point('kv.migrate',
                                reason=reason) is faults.DROP:
                    raise RuntimeError('injected kv.migrate drop')
                tokens = [int(t) for t in rec.get('tokens') or []]
                if not tokens:
                    raise RuntimeError('empty migration record')
                remaining = int(rec.get('limit', 0)) - len(tokens)
                if remaining <= 0:
                    raise RuntimeError('no generation budget left')
                peer = target
                if peer is None:
                    from skypilot_tpu.inference import affinity
                    eng = next(iter(rt.live_engines()), None)
                    key = None
                    if eng is not None and getattr(eng, 'paged',
                                                   False):
                        key = affinity.token_affinity_key(
                            tokens, eng.page_size,
                            salt=affinity.adapter_salt(
                                rec.get('adapter')))
                    peer = rt.pick_decode_peer(key)
                if not peer:
                    raise RuntimeError('no migration peer available')
                inner = {'tokens': [tokens],
                         'max_new_tokens': remaining,
                         'temperature': rec.get('temperature', 0.0),
                         'top_k': rec.get('top_k', 0),
                         'top_p': rec.get('top_p', 1.0),
                         'stop_token_ids':
                             rec.get('stop_token_ids') or [],
                         'stream': bool(stream)}
                if rec.get('adapter'):
                    inner['model'] = rec['adapter']
                if rec.get('deadline_s'):
                    inner['timeout'] = rec['deadline_s']
                body = {'path': '/generate', 'request': inner,
                        'reason': reason}
                if rec.get('payload'):
                    body['payload'] = base64.b64encode(
                        rec['payload']).decode()
                ctx = getattr(self, '_trace_ctx', None)
                hdrs = ({tracing.HEADER: tracing.format_header(ctx)}
                        if ctx is not None else None)
                read_timeout = float(rec.get('deadline_s') or
                                     rt.request_timeout) + 60.0
                with tracing.span('kv.migrate', ctx, peer=peer,
                                  reason=reason):
                    upstream = requests_lib.post(
                        f'http://{peer}/kv/migrate', json=body,
                        headers=hdrs, stream=True,
                        timeout=(3.0, read_timeout))
                if upstream.status_code != 200:
                    code = upstream.status_code
                    upstream.close()
                    raise RuntimeError(
                        f'migration peer {peer} answered {code}')
            except Exception as e:  # pylint: disable=broad-except
                rt.record_migration(reason, time.monotonic() - t0,
                                    ok=False)
                print(f'kv migrate failed ({type(e).__name__}: {e}); '
                      f'resuming locally', flush=True)
                return None
            rt.record_migration(reason, time.monotonic() - t0,
                                ok=True)
            return upstream

        def _resume_record(self, rec, depth: int = 0):
            """Finish one evacuated (non-streaming) session: try the
            peer ship, fall back to a local warm resume. Returns the
            full token row (prompt + all generated)."""
            upstream = self._migrate_record(rec, stream=False)
            if upstream is not None:
                try:
                    with upstream:
                        out = upstream.json()
                    rows = out.get('tokens') or []
                    if rows and isinstance(rows[0], list):
                        return [int(t) for t in rows[0]]
                except Exception as e:  # pylint: disable=broad-except
                    print(f'kv migrate response unusable '
                          f'({type(e).__name__}: {e}); resuming '
                          f'locally', flush=True)
            return self._resume_locally(rec, depth=depth)

        def _resume_locally(self, rec, depth: int = 0):
            """Local warm resume of an evacuated session: resubmit
            the committed tokens — their full pages were promoted
            into the prefix cache at evacuation, so admission is a
            prefix-cache hit and only the sub-page tail recomputes.
            A second evacuation mid-resume retries the whole ladder
            (bounded); success counts as a 'local_fallback'
            migration."""
            tokens = [int(t) for t in rec.get('tokens') or []]
            remaining = max(int(rec.get('limit', 0)) - len(tokens), 1)
            adapter = rec.get('adapter')
            eng = rt.engine_for(adapter)
            if eng is None:
                return tokens  # one-shot runtime: nothing to resume
            deadline_s = (float(rec.get('deadline_s') or 0)
                          or rt.request_timeout)
            t0 = time.monotonic()
            try:
                fut = eng.submit(
                    tokens, max_new_tokens=remaining,
                    temperature=rec.get('temperature', 0.0),
                    top_k=rec.get('top_k', 0),
                    top_p=rec.get('top_p', 1.0),
                    stop_token_ids=list(
                        rec.get('stop_token_ids') or []),
                    deadline_s=deadline_s, adapter=adapter,
                    trace_ctx=getattr(self, '_trace_ctx', None))
                row = fut.result(timeout=deadline_s + 30.0)
            except SessionMigratedError as me:
                if depth >= 2:
                    raise
                return self._resume_record(me.record, depth=depth + 1)
            rt.record_migration('local_fallback',
                                time.monotonic() - t0, ok=True)
            return row

        def _maybe_handoff(self, path, req) -> bool:
            """Prefill-role disaggregation: prefill the prompt
            locally (1-token generation — its pages promote into the
            prefix cache), export the page chain, POST it with the
            original request to the affinity-assigned decode peer,
            and proxy that peer's response back. True = the client
            was fully answered from the decode pool. ANY failure —
            injected kv.handoff fault, unreachable peer, decode-side
            shed (429/503) — returns False and the caller serves the
            request locally from the already-warm pages (graceful
            fallback, never a client-visible error)."""
            peers = rt.decode_peers()
            eng = rt.engine
            if not peers or eng is None or \
                    not getattr(eng, 'prefix_caching', False):
                return False
            if path not in ('/generate', '/v1/generate'):
                # Text endpoints have no token ids here; they serve
                # locally on the prefill replica (the LB's length
                # threshold only routes token requests this way).
                return False
            rows = req.get('tokens') or []
            if not rows or not isinstance(rows[0], list) or \
                    len(rows) != 1:
                return False  # batch rows: local (no chain per row)
            import base64

            import requests as requests_lib

            from skypilot_tpu.inference import affinity
            ctx = getattr(self, '_trace_ctx', None)
            t0 = time.monotonic()
            nbytes = 0
            try:
                if faults.point('kv.handoff') is faults.DROP:
                    raise RuntimeError('injected kv.handoff drop')
                row = [int(t) for t in rows[0]]
                adapter = rt.resolve_model(req.get('model'))
                deadline_s = rt.deadline_for(req)
                limit = rt.limit_for(0.0, streaming=True)
                if len(row) >= limit:
                    return False  # the handler's 400 to give
                # Local prefill: ONE generated token forces the
                # prompt through the (chunked) prefill path and
                # promotes its full pages into the prefix cache.
                eng.submit(row, max_new_tokens=1, temperature=0.0,
                           deadline_s=deadline_s,
                           adapter=adapter,
                           trace_ctx=ctx).result(
                               timeout=deadline_s + 30.0)
                with tracing.span('kv.export', ctx) as sp:
                    data = eng.export_chain(row, adapter=adapter)
                    sp.add(bytes=len(data))
                if not data:
                    return False  # sub-page prompt: nothing to ship
                key = affinity.token_affinity_key(
                    row, eng.page_size,
                    salt=affinity.adapter_salt(req.get('model')))
                peer = rt.pick_decode_peer(key)
                if peer is None:
                    return False
                nbytes = len(data)
                # The trace rides the handoff: the decode peer's
                # root span adopts this trace_id, completing the
                # LB -> prefill -> decode chain.
                hdrs = ({tracing.HEADER: tracing.format_header(ctx)}
                        if ctx is not None else None)
                with tracing.span('kv.post', ctx, peer=peer,
                                  bytes=nbytes):
                    upstream = requests_lib.post(
                        f'http://{peer}/kv/import',
                        json={'payload':
                              base64.b64encode(data).decode(),
                              'path': path, 'request': req},
                        headers=hdrs,
                        stream=True,
                        timeout=(3.0, deadline_s + 60.0))
                if upstream.status_code in (429, 500, 502, 503):
                    code = upstream.status_code
                    upstream.close()
                    raise RuntimeError(
                        f'decode replica {peer} answered {code}')
            except Exception as e:  # pylint: disable=broad-except
                rt.record_handoff(time.monotonic() - t0, nbytes,
                                  ok=False)
                print(f'kv handoff failed ({type(e).__name__}: {e}); '
                      f'serving locally', flush=True)
                return False
            # Stream the decode replica's response through. Headers
            # out = the handoff is committed; a mid-stream death
            # truncates exactly like a direct replica death would.
            rt.record_handoff(time.monotonic() - t0, nbytes, ok=True)
            with upstream:
                self.send_response(upstream.status_code)
                ctype = upstream.headers.get('Content-Type',
                                             'application/json')
                self.send_header('Content-Type', ctype)
                body_bytes = None
                if 'text/event-stream' not in ctype:
                    body_bytes = upstream.content
                    self.send_header('Content-Length',
                                     str(len(body_bytes)))
                self.end_headers()
                if body_bytes is not None:
                    self.wfile.write(body_bytes)
                    return True
                self._sse_open = True
                eof, _first = sse.pipe(upstream, self.wfile)
                if not eof:
                    print('kv handoff stream truncated', flush=True)
            return True

        def _generate(self):
            try:
                req = self._read_body()
                tokens = req['tokens']
                temperature = float(req.get('temperature', 0.0))
                top_k = int(req.get('top_k', 0))
                top_p = float(req.get('top_p', 1.0))
                stop_ids = [int(t) for t in
                            req.get('stop_token_ids', [])]
                stream = bool(req.get('stream'))
                # `model` selects a LoRA adapter (unknown -> 404; base
                # name / absent -> base model).
                adapter = rt.resolve_model(req.get('model'))
                deadline_s = rt.deadline_for(req)
                limit = rt.limit_for(temperature, streaming=stream)
                for row in tokens:
                    if len(row) >= limit:
                        raise ValueError(
                            f'prompt len {len(row)} >= max_total_len '
                            f'{limit}')
                max_new = int(req.get('max_new_tokens',
                                      rt.engine_total))
                if stream:
                    self._generate_stream(tokens, max_new, temperature,
                                          top_k, top_p, stop_ids,
                                          deadline_s, adapter)
                    return
                t0 = time.monotonic()
                ttft = None
                eng = rt.engine_for(adapter)
                if eng is not None:
                    # Ragged rows welcome: each joins the shared
                    # decode loop independently. The shared latch
                    # records TTFT at the request's FIRST committed
                    # token (any row) — non-streaming requests get
                    # real TTFT too, not just streamed ones.
                    latch = obs_catalog.FirstTokenLatch()
                    futs = _submit_all(
                        eng,
                        [[int(t) for t in row] for row in tokens],
                        max_new_tokens=max_new,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, stop_token_ids=stop_ids,
                        on_token=latch, deadline_s=deadline_s,
                        adapter=adapter,
                        trace_ctx=getattr(self, '_trace_ctx', None))
                    # The engine's deadline sweep resolves expired
                    # futures with DeadlineExceededError (-> 504); the
                    # host-side timeout is only a backstop. A future
                    # resolving with SessionMigratedError means the
                    # engine evacuated the slot (drain / preemption /
                    # rebalance): finish that row on a peer, or
                    # locally on the promoted warm pages.
                    rows = []
                    for f in futs:
                        try:
                            rows.append(f.result(
                                timeout=deadline_s + 30.0))
                        except SessionMigratedError as me:
                            rows.append(self._resume_record(
                                me.record))
                    ttft = latch.first_token_s
                else:
                    import jax
                    import jax.numpy as jnp
                    prompt = jnp.asarray(tokens, jnp.int32)
                    if prompt.ndim != 2:
                        raise ValueError(
                            'tokens must be [batch, prompt_len]')
                    fn = rt.get_fn(prompt.shape[0], temperature)
                    out = fn(rt.params, prompt, rt.split_rng())
                    rows = jax.device_get(out).tolist()
                # One-shot rows come back padded to the full jit
                # bucket: the DECODED count is bounded by max_new,
                # not the buffer tail (metrics feed /stats tok/s).
                n_gen = sum(min(max(len(r) - len(p), 0), max_new)
                            for r, p in zip(rows, tokens))
                rt.metrics.record(time.monotonic() - t0, n_gen,
                                  ttft_s=ttft,
                                  n_prompt_tokens=sum(
                                      len(p) for p in tokens))
                self._json({'tokens': rows})
            except Exception as e:  # pylint: disable=broad-except
                self._plain_error(e)

        def _robustness_accounting(self, e: Exception):
            """(code, headers) for a failed request + the shed /
            deadline counters (window stats + Prometheus)."""
            code, retry_after = classify_error(e)
            if code == 429:
                rt.metrics.record_shed()
            elif code == 504:
                rt.metrics.record_deadline_exceeded()
            elif code == 503 and rt.metrics.slo is not None:
                # Engine-dead / adapter-load failures are server
                # errors: they burn error budget (429/504 already
                # burn through their own hooks; 4xx client errors
                # never do).
                rt.metrics.slo.record_request(error=True)
            headers = ({'Retry-After': str(max(1, int(retry_after)))}
                       if retry_after is not None else None)
            return code, headers

        def _plain_error(self, e: Exception):
            code, headers = self._robustness_accounting(e)
            if getattr(self, '_sse_open', False):
                # Mid-stream failure: headers are out; close the
                # stream (the client sees truncation, not a reset).
                try:
                    self.sse_done()
                except Exception:  # pylint: disable=broad-except  # stpu: ignore[SKY005] — closing an already-broken stream; client is gone
                    pass
                return
            self._json({'error': f'{type(e).__name__}: {e}'}, code,
                       headers=headers)

        def _generate_stream(self, tokens, max_new, temperature,
                             top_k, top_p, stop_ids, deadline_s,
                             adapter=None):
            """SSE of {"index": row, "token": id} events, one per
            committed token across all rows, interleaved by arrival."""
            t0 = time.monotonic()
            handles = [rt.submit_stream(
                [int(t) for t in row], max_new, temperature,
                top_k=top_k, top_p=top_p, stop_token_ids=stop_ids,
                deadline_s=deadline_s, adapter=adapter,
                trace_ctx=getattr(self, '_trace_ctx', None))
                for row in tokens]
            self.sse_start(handles)
            n_gen = 0
            ttft = None
            migrated = False
            # ITL is recorded at engine commit time by the handles'
            # on_token (StreamHandle), not at SSE delivery.
            try:
                try:
                    for i, t in iter_interleaved(handles):
                        if ttft is None:
                            ttft = time.monotonic() - t0
                        n_gen += 1
                        self.sse_send({'index': i, 'token': t})
                except SessionMigratedError:
                    # The engine evacuated the slots mid-stream. The
                    # interleaver drained every already-committed
                    # token first, so the client is exactly caught up
                    # with the committed sequence — finish the tail
                    # from a peer (or locally) below.
                    migrated = True
            finally:
                rt.cancel_streams(handles)  # no-op when completed
            if migrated:
                final_rows = self._finish_migrated_stream(handles)
                if final_rows is None:
                    # Fully proxied: the peer's SSE tail (terminal
                    # event + [DONE] included) already went out.
                    rt.metrics.record(time.monotonic() - t0, n_gen,
                                      ttft_s=ttft,
                                      n_prompt_tokens=sum(
                                          len(row) for row in tokens))
                    return
            else:
                final_rows = [h.future.result() for h in handles]
            # Full rows in the terminal event: stream consumers get
            # the same payload the non-streaming endpoint returns.
            # Under `--stream-final lengths` it gives the rows' lengths
            # instead: the stream has carried every generated token
            # and the prompt is the caller's own, and a 14k-token row
            # is an 85 KB line, past what a line-buffered client takes
            # (asyncio's stream reader stops at 64 KiB).
            if rt.stream_final == 'lengths':
                self.sse_send({'done': True,
                               'lengths': [len(r) for r in final_rows]})
            else:
                self.sse_send({'done': True, 'tokens': final_rows})
            self.sse_done()
            rt.metrics.record(time.monotonic() - t0, n_gen,
                              ttft_s=ttft,
                              n_prompt_tokens=sum(
                                  len(row) for row in tokens))

        def _finish_migrated_stream(self, handles):
            """Finish an SSE /generate stream whose slots were
            evacuated mid-flight. Single-row streams proxy the peer's
            SSE tail straight through (same {'index': 0, ...} frame
            shape, terminal event included) — returns None. Multi-row
            streams, and any ship failure, resume locally: the
            continuation tokens keep streaming under their original
            row indices and the full rows come back for the terminal
            event."""
            outcomes = []
            for h in handles:
                try:
                    outcomes.append(('done',
                                     h.future.result(timeout=0.001)))
                except SessionMigratedError as me:
                    outcomes.append(('rec', me.record))
            recs = [(i, o[1]) for i, o in enumerate(outcomes)
                    if o[0] == 'rec']
            if len(handles) == 1 and recs:
                upstream = self._migrate_record(recs[0][1],
                                                stream=True)
                if upstream is not None:
                    with upstream:
                        eof, _first = sse.pipe(upstream, self.wfile)
                        if not eof:
                            print('migration stream truncated',
                                  flush=True)
                    return None
            rows = [o[1] if o[0] == 'done' else None
                    for o in outcomes]
            for i, rec in recs:
                rows[i] = self._resume_stream_locally(i, rec)
            return rows

        def _resume_stream_locally(self, index, rec):
            """Local warm resume of one evacuated streaming row:
            resubmit the committed tokens (prefix-cache hit on the
            promoted pages) and keep streaming the NEW tokens under
            the row's original index. Returns the full row; a repeat
            evacuation or failure returns the committed row as-is
            (the stream truncates at the committed point, exactly
            like a replica death would)."""
            tokens = [int(t) for t in rec.get('tokens') or []]
            remaining = max(int(rec.get('limit', 0)) - len(tokens), 1)
            deadline_s = (float(rec.get('deadline_s') or 0)
                          or rt.request_timeout)
            t0 = time.monotonic()
            try:
                h = rt.submit_stream(
                    tokens, remaining,
                    rec.get('temperature', 0.0),
                    top_k=rec.get('top_k', 0),
                    top_p=rec.get('top_p', 1.0),
                    stop_token_ids=list(
                        rec.get('stop_token_ids') or []),
                    deadline_s=deadline_s,
                    adapter=rec.get('adapter'),
                    trace_ctx=getattr(self, '_trace_ctx', None))
            except Exception as e:  # pylint: disable=broad-except
                print(f'local stream resume failed to submit '
                      f'({type(e).__name__}: {e}); stream truncates '
                      f'at the committed point', flush=True)
                return tokens
            try:
                for _j, t in iter_interleaved([h]):
                    self.sse_send({'index': index, 'token': t})
                row = h.future.result(timeout=deadline_s + 30.0)
            except Exception as e:  # pylint: disable=broad-except
                print(f'local stream resume failed '
                      f'({type(e).__name__}: {e}); stream truncates '
                      f'at the committed point', flush=True)
                rt.cancel_streams([h])
                return tokens
            rt.record_migration('local_fallback',
                                time.monotonic() - t0, ok=True)
            return row

        def _openai_completions(self):
            try:
                body = self._read_body()
                req = oai.CompletionRequest(
                    prompts=oai.normalize_prompts(
                        body.get('prompt', '')),
                    max_new=int(body.get('max_tokens', 16)),
                    temperature=float(body.get('temperature', 1.0)),
                    top_p=float(body.get('top_p', 1.0)),
                    stop_strings=body.get('stop') or [],
                    n=int(body.get('n', 1)),
                    stream=bool(body.get('stream')),
                    logprobs=body.get('logprobs'),
                    echo=bool(body.get('echo')),
                    deadline_s=rt.deadline_for(body),
                    adapter=rt.resolve_model(body.get('model')),
                    model=body.get('model'))
                try:
                    if req.stream:
                        oai.stream_completion(rt, req, self)
                    else:
                        self._json(oai.run_completion(rt, req))
                except SessionMigratedError:
                    # Evacuated mid-request: replay on the promoted
                    # warm pages (the prompt prefill is a prefix-cache
                    # hit). Mid-stream there is no replay — headers
                    # are out; _oai_error truncates the stream.
                    if getattr(self, '_sse_open', False):
                        raise
                    if req.stream:
                        oai.stream_completion(rt, req, self)
                    else:
                        self._json(oai.run_completion(rt, req))
            except Exception as e:  # pylint: disable=broad-except
                self._oai_error(e)

        def _openai_chat(self):
            try:
                body = self._read_body()
                # Model validation FIRST: an unknown model must 404
                # before prompt rendering can fail 400 on tokenizer
                # details.
                adapter = rt.resolve_model(body.get('model'))
                prompt = oai.render_chat_prompt(rt, body['messages'])
                # Modern chat knobs: logprobs is a bool +
                # top_logprobs count (clamped to the engine's 5).
                chat_lp = None
                if body.get('logprobs'):
                    chat_lp = min(int(body.get('top_logprobs', 0)), 5)
                req = oai.CompletionRequest(
                    prompts=[prompt],
                    max_new=int(body.get('max_tokens', 16)),
                    temperature=float(body.get('temperature', 1.0)),
                    top_p=float(body.get('top_p', 1.0)),
                    stop_strings=body.get('stop') or [],
                    n=int(body.get('n', 1)),
                    stream=bool(body.get('stream')),
                    logprobs=chat_lp,
                    deadline_s=rt.deadline_for(body),
                    adapter=adapter,
                    model=body.get('model'))
                try:
                    if req.stream:
                        oai.stream_completion(rt, req, self,
                                              chat=True)
                    else:
                        self._json(oai.to_chat_response(
                            oai.run_completion(rt, req)))
                except SessionMigratedError:
                    # Same warm-replay contract as /v1/completions.
                    if getattr(self, '_sse_open', False):
                        raise
                    if req.stream:
                        oai.stream_completion(rt, req, self,
                                              chat=True)
                    else:
                        self._json(oai.to_chat_response(
                            oai.run_completion(rt, req)))
            except Exception as e:  # pylint: disable=broad-except
                self._oai_error(e)

        def _oai_error(self, e: Exception):
            code, headers = self._robustness_accounting(e)
            if getattr(self, '_sse_open', False):
                # Headers already sent: the OpenAI stream contract has
                # no in-band error frame; close the stream.
                try:
                    self.sse_done()
                except Exception:  # pylint: disable=broad-except  # stpu: ignore[SKY005] — closing an already-broken stream; client is gone
                    pass
                return
            err_type = {429: 'rate_limit_exceeded',
                        503: 'service_unavailable',
                        504: 'timeout'}.get(code,
                                            'invalid_request_error')
            err = {'message': f'{type(e).__name__}: {e}',
                   'type': err_type}
            if code == 404:
                # The OpenAI unknown-model error object.
                err['code'] = 'model_not_found'
            self._json({'error': err}, code, headers=headers)

        def _generate_text(self):
            try:
                tok = rt.get_tokenizer()
                req = self._read_body()
                prompts = req['prompts']
                if isinstance(prompts, str):
                    prompts = [prompts]
                temperature = float(req.get('temperature', 0.0))
                top_k = int(req.get('top_k', 0))
                top_p = float(req.get('top_p', 1.0))
                stop_strings = req.get('stop') or []
                if isinstance(stop_strings, str):
                    stop_strings = [stop_strings]
                max_new = int(req.get('max_new_tokens', 64))
                stream = bool(req.get('stream'))
                adapter = rt.resolve_model(req.get('model'))
                deadline_s = rt.deadline_for(req)
                encoded = [tok(p)['input_ids'] for p in prompts]
                limit = rt.limit_for(temperature, streaming=stream)
                for ids in encoded:
                    if len(ids) >= limit:
                        raise ValueError(
                            f'prompt tokenizes to {len(ids)} >= '
                            f'max_total_len {limit}')
                if stream:
                    self._generate_text_stream(
                        encoded, max_new, temperature, top_k, top_p,
                        stop_strings, deadline_s, adapter)
                    return
                t0 = time.monotonic()
                ttft = None
                eng = rt.engine_for(adapter)
                if eng is not None:
                    latch = obs_catalog.FirstTokenLatch()
                    futs = _submit_all(
                        eng, encoded, max_new_tokens=max_new,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, on_token=latch,
                        deadline_s=deadline_s, adapter=adapter,
                        trace_ctx=getattr(self, '_trace_ctx', None))
                    rows = []
                    for f in futs:
                        try:
                            rows.append(f.result(
                                timeout=deadline_s + 30.0))
                        except SessionMigratedError as me:
                            rows.append(self._resume_record(
                                me.record))
                    ttft = latch.first_token_s
                else:
                    rows = rt.one_shot_rows(encoded, max_new,
                                            temperature)
                texts = [tok.decode(row[len(ids):],
                                    skip_special_tokens=True)
                         for ids, row in zip(encoded, rows)]
                texts = [oai.trim_stops(t, stop_strings)[0]
                         for t in texts]
                n_gen = sum(len(r) - len(p)
                            for r, p in zip(rows, encoded))
                rt.metrics.record(time.monotonic() - t0, n_gen,
                                  ttft_s=ttft,
                                  n_prompt_tokens=sum(
                                      len(p) for p in encoded))
                self._json({'texts': texts})
            except Exception as e:  # pylint: disable=broad-except
                self._plain_error(e)

        def _generate_text_stream(self, encoded: List[List[int]],
                                  max_new, temperature, top_k, top_p,
                                  stop_strings, deadline_s,
                                  adapter=None):
            """SSE of {"index": i, "delta": text} events (incremental
            detokenization + stop-string holdback per row)."""
            tok = rt.get_tokenizer()
            t0 = time.monotonic()
            handles = [rt.submit_stream(
                ids, max_new, temperature, top_k=top_k, top_p=top_p,
                deadline_s=deadline_s, adapter=adapter,
                trace_ctx=getattr(self, '_trace_ctx', None))
                       for ids in encoded]
            self.sse_start(handles)
            decs = [oai.IncrementalDecoder(tok) for _ in encoded]
            scans = [oai.StopStringScanner(stop_strings)
                     for _ in encoded]
            n_gen = 0
            ttft = None
            migrated = False
            try:
                try:
                    for i, t in iter_interleaved(handles):
                        if ttft is None:
                            ttft = time.monotonic() - t0
                        n_gen += 1
                        if scans[i].hit:
                            continue
                        out = scans[i].push(decs[i].push(t))
                        if out:
                            self.sse_send({'index': i, 'delta': out})
                except SessionMigratedError:
                    migrated = True
            finally:
                rt.cancel_streams(handles)  # no-op when completed
            if migrated:
                # Evacuated mid-stream: the committed deltas already
                # went out; finish each migrated row locally on the
                # promoted warm pages (text endpoints never ship —
                # the peer path is token-request only).
                for i, h in enumerate(handles):
                    try:
                        h.future.result(timeout=0.001)
                    except SessionMigratedError as me:
                        self._resume_text_stream_locally(
                            i, me.record, decs, scans)
                    except Exception as e:  # pylint: disable=broad-except
                        # Row failed for a non-migration reason: the
                        # stream truncates for it, like the pre-
                        # migration behavior.
                        print(f'text stream row {i} failed during '
                              f'evacuation ({type(e).__name__}: {e})',
                              flush=True)
            for i in range(len(handles)):
                if not scans[i].hit:
                    out = (scans[i].push(decs[i].flush()) +
                           scans[i].flush())
                    if out:
                        self.sse_send({'index': i, 'delta': out})
            self.sse_done()
            rt.metrics.record(time.monotonic() - t0, n_gen,
                              ttft_s=ttft,
                              n_prompt_tokens=sum(
                                  len(ids) for ids in encoded))

        def _resume_text_stream_locally(self, index, rec, decs,
                                        scans):
            """Local warm resume of one evacuated text-stream row:
            continuation tokens run through the row's incremental
            decoder + stop scanner so the delta stream picks up
            exactly where it left off."""
            tokens = [int(t) for t in rec.get('tokens') or []]
            remaining = max(int(rec.get('limit', 0)) - len(tokens), 1)
            deadline_s = (float(rec.get('deadline_s') or 0)
                          or rt.request_timeout)
            t0 = time.monotonic()
            try:
                h = rt.submit_stream(
                    tokens, remaining,
                    rec.get('temperature', 0.0),
                    top_k=rec.get('top_k', 0),
                    top_p=rec.get('top_p', 1.0),
                    deadline_s=deadline_s,
                    adapter=rec.get('adapter'),
                    trace_ctx=getattr(self, '_trace_ctx', None))
            except Exception as e:  # pylint: disable=broad-except
                print(f'local text-stream resume failed to submit '
                      f'({type(e).__name__}: {e}); row {index} '
                      f'truncates at the committed point', flush=True)
                return
            try:
                for _j, t in iter_interleaved([h]):
                    if scans[index].hit:
                        continue
                    out = scans[index].push(decs[index].push(t))
                    if out:
                        self.sse_send({'index': index, 'delta': out})
            except Exception as e:  # pylint: disable=broad-except
                print(f'local text-stream resume failed '
                      f'({type(e).__name__}: {e}); row {index} '
                      f'truncates at the committed point', flush=True)
                rt.cancel_streams([h])
                return
            rt.record_migration('local_fallback',
                                time.monotonic() - t0, ok=True)

    server = ThreadingHTTPServer(('0.0.0.0', port), Handler)
    server.inflight = _inflight            # type: ignore[attr-defined]
    server.inflight_lock = _inflight_lock  # type: ignore[attr-defined]
    server.draining = _draining            # type: ignore[attr-defined]
    return server


class ServePreemptionNotice(train_guard.PreemptionNotice):
    """Serving-side preemption watcher: the trainer's GCE-metadata
    poll + injectable notice (robustness/train_guard.py), firing the
    `serve.preempt_notice` fault point instead of the trainer's —
    zone-scoped drop rules are how decode_zone_storm.json preempts
    one spot pool without touching the rest of the fleet. SIGTERM
    stays with serve()'s own drain handler (install_sigterm=False),
    which evacuates too; this watcher covers the ~30s metadata notice
    that arrives BEFORE the SIGTERM on GCE spot VMs."""

    def trigger(self, reason: str) -> None:
        # Latch only: the train-plane notice counter stays a train
        # metric; serving preemptions are visible through the
        # migration counters the evacuation path ticks.
        if not self.notice.is_set():
            self.reason = reason
            self.notice.set()

    def _poll_loop(self) -> None:
        while not self._stop.is_set() and not self.notice.is_set():
            self.polls += 1
            if faults.point('serve.preempt_notice',
                            **self.ctx) is faults.DROP:
                self.trigger('injected')
                break
            if self._probe_metadata():
                self.trigger('metadata')
                break
            self._stop.wait(self.poll_interval_s)


def evacuate_for_exit(rt: InferenceRuntime,
                      reason: str = 'drain') -> dict:
    """Mass chain evacuation ahead of process exit (SIGTERM drain or
    preemption notice): every live engine's active sessions resolve
    with SessionMigratedError, and their owning HTTP threads ship the
    chains to peers / finish locally on the promoted pages. Failures
    are logged, never raised — a broken engine must not stop the
    drain from completing."""
    total = {'evacuated': 0, 'chains': 0, 'queued': 0}
    for eng in rt.live_engines():
        fn = getattr(eng, 'evacuate_chains', None)
        if fn is None:
            continue
        try:
            s = fn(reason=reason)
        except Exception as e:  # pylint: disable=broad-except
            print(f'evacuation failed on an engine '
                  f'({type(e).__name__}: {e}); its sessions finish '
                  f'locally', flush=True)
            continue
        for k in total:
            total[k] += int(s.get(k, 0))
    if total['evacuated'] or total['queued']:
        rt.record_evacuation(total)
        print(f'serve_lm: evacuated {total["evacuated"]} active + '
              f'{total["queued"]} queued sessions '
              f'({total["chains"]} KV chains packed, '
              f'reason={reason})', flush=True)
    return total


def drain(server: ThreadingHTTPServer, rt: InferenceRuntime,
          drain_grace: float, straggler_grace: float = 0.5,
          exit_fn=os._exit) -> None:
    """Graceful drain: flip /readyz to 503 (readiness probes pull the
    replica out of rotation), evacuate every active KV chain (the
    owning HTTP threads migrate the sessions to peers — in-flight
    POSTs the wait below covers — or finish them locally), let the
    accept loop pick up stragglers for `straggler_grace`, stop
    accepting, wait for in-flight POSTs (bounded by `drain_grace`),
    exit 0 — a mid-generation client must not see a reset because the
    controller culled this replica. `exit_fn` is injectable so the
    drain contract is testable without killing the test process."""
    server.draining.set()
    print('serve_lm: SIGTERM — draining in-flight requests',
          flush=True)
    # Drain-by-migration (idempotent: a controller that already
    # POSTed /kv/evacuate left the engines empty, and this finds
    # nothing). Failure falls back to the classic local-finish drain.
    evacuate_for_exit(rt, reason='drain')
    time.sleep(straggler_grace)  # stragglers: accept loop gets them
    server.shutdown()   # stops accepting; handlers keep running
    deadline = time.monotonic() + drain_grace
    while time.monotonic() < deadline:
        with server.inflight_lock:
            if server.inflight['n'] == 0:
                break
        time.sleep(0.05)
    rt.stop()
    # exit_fn defaults to os._exit: skip the XLA C++ teardown
    # entirely — destructor ordering under an in-flight device stream
    # SIGABRTs nondeterministically (the drain is complete; there is
    # nothing left to clean up).
    exit_fn(0)


def serve(rt: InferenceRuntime, port: int,
          drain_grace: float = 630.0, zone: str = '',
          watch_preemption: bool = True) -> None:
    """Run the HTTP server until killed. `drain_grace` bounds the
    SIGTERM drain wait; it defaults ABOVE the 600s request-timeout
    default so a worst-case in-flight generation still completes —
    requests longer than the grace window are dropped at exit.
    `zone` labels the replica's placement (spot decode pools) and
    scopes the preemption watcher's fault context; the watcher turns
    a GCE preemption notice — or an injected `serve.preempt_notice`
    drop — into mass chain evacuation followed by the normal drain,
    all inside the ~30s grace window."""
    server = make_server(rt, port)

    _term = threading.Event()

    def _drain_loop():
        """All drain work happens on this pre-started thread; the
        signal handler only sets an event (anything heavier in the
        signal frame proved crash-prone against the XLA runtime's own
        thread machinery)."""
        _term.wait()
        drain(server, rt, drain_grace)

    threading.Thread(target=_drain_loop, daemon=True).start()
    signal.signal(signal.SIGTERM, lambda *_: _term.set())
    if watch_preemption:
        ctx = {'zone': zone} if zone else {}
        notice = ServePreemptionNotice(poll_interval_s=2.0,
                                       install_sigterm=False,
                                       ctx=ctx)
        notice.start()

        def _preempt_watch():
            notice.notice.wait()
            print(f'serve_lm: preemption notice ({notice.reason}) — '
                  f'evacuating active sessions', flush=True)
            rt.set_evacuation_hint('preempt', None)
            evacuate_for_exit(rt, reason='preempt')
            _term.set()  # the drain loop finishes the exit

        threading.Thread(target=_preempt_watch, daemon=True).start()
    print(f'serve_lm listening on :{port} model={rt.model_name}',
          flush=True)
    server.serve_forever()
