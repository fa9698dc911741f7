#!/usr/bin/env python
"""Serving benchmark: req/s + TTFT through the LM inference server.

BASELINE.md north-star #4 ('SkyServe req/s + p50 TTFT'). Drives
recipes/serve_lm.py over HTTP with concurrent closed-loop clients and
reports request throughput and time-to-first-token percentiles, for
both engines:

  python benchmarks/serve_bench.py --engine continuous --requests 64
  python benchmarks/serve_bench.py --engine simple --requests 64

On CPU this exercises the full serving stack with llama-tiny; on a
TPU host pass --model llama3-8b (weights via --ckpt-dir). Prints one
JSON line per run.

TTFT is measured for real over SSE (`stream: true` — the first token
frame's arrival), not a 1-token proxy round-trip. Note: in simple
(one-shot) mode streamed requests ride the lazily-built slot engine —
the product's actual streaming path for that configuration.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import socket
import subprocess
import sys
import threading
import time

import requests

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # fleet mode imports skypilot_tpu in-process


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def pct_ms(sorted_vals, q):
    """Linear-interpolated percentile of sorted SECONDS, in ms.
    Nearest-rank at bench-sized N collapsed distinct percentiles
    onto one sample (BENCH_lora_r10's p95_ttft 1480.4 vs p99 1482.62
    were the same observation); interpolation keeps them honest —
    always read them next to the block's n_samples."""
    if not sorted_vals:
        return None
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return round(1000.0 * (sorted_vals[lo] * (1.0 - frac) +
                           sorted_vals[hi] * frac), 2)


def _slo_observed(record: dict) -> dict:
    """Map a bench record's measured keys onto SLO dimensions.
    Engine-side ITL (decode_itl_ms_p99 / server itl_ms_p99) beats the
    SSE-timing fallback — wire jitter is not a scheduler promise.
    Errors fold in server-reported 504s so single-server and fleet
    records score the same promise."""
    requests = record.get('requests') or 0
    itl = record.get('decode_itl_ms_p99')
    if itl is None:
        itl = record.get('itl_ms_p99')
    if itl is None:
        itl = record.get('sse_itl_ms_p99')
    errors = record.get('client_errors')
    deadline = record.get('server_deadline_exceeded')
    error_rate = None
    if requests and (errors is not None or deadline is not None):
        error_rate = ((errors or 0) + (deadline or 0)) / float(requests)
    shed = record.get('shed_requests')
    shed_rate = None
    if shed is not None and (requests + shed) > 0:
        shed_rate = shed / float(requests + shed)
    return {
        'p99_ttft_ms': record.get('p99_ttft_ms'),
        'p99_itl_ms': itl,
        'error_rate': error_rate,
        'shed_rate': shed_rate,
    }


def attach_slo(record: dict, targets: dict) -> dict:
    """Score a bench record (or each entry of an A/B `runs` map)
    against `targets` and attach the machine-checkable `slo` block —
    only the targeted dimensions are scored; an unmeasured targeted
    dimension fails (slo.evaluate's contract)."""
    from skypilot_tpu.observability import slo as slo_lib
    if not isinstance(record, dict):
        return record
    runs = record.get('runs')
    if isinstance(runs, dict):
        for run in runs.values():
            attach_slo(run, targets)
        record['slo'] = {
            'ok': all(bool((r or {}).get('slo', {}).get('ok'))
                      for r in runs.values()),
            'runs': {name: (r or {}).get('slo', {}).get('ok')
                     for name, r in runs.items()},
        }
        return record
    observed = {dim: val for dim, val in _slo_observed(record).items()
                if dim in targets}
    record['slo'] = slo_lib.evaluate(targets, observed)
    return record


def _server_env(args) -> dict:
    """Environment for a spawned serve_lm: repo on PYTHONPATH, and —
    for --tensor N on CPU — N virtual host devices (the ROADMAP
    multi-device-without-TPUs harness)."""
    env = dict(os.environ)
    env['PYTHONPATH'] = f"{REPO}:{env.get('PYTHONPATH', '')}"
    chips = max(args.tensor, 1) * max(getattr(args, 'stages', 1), 1)
    if chips > 1:
        flags = env.get('XLA_FLAGS', '')
        if '--xla_force_host_platform_device_count' not in flags:
            env['XLA_FLAGS'] = (
                f'{flags} --xla_force_host_platform_device_count='
                f'{chips}').strip()
    return env


def _build_server_cmd(args, adapter_dir=None) -> list:
    """serve_lm command line WITHOUT --port (single-server mode
    appends one; fleet mode lets the replica manager assign them)."""
    cmd = [sys.executable, '-m', 'skypilot_tpu.recipes.serve_lm',
           '--model', args.model,
           '--max-total-len', str(args.max_total_len)]
    if args.kv_dtype:
        cmd += ['--kv-dtype', args.kv_dtype]
    if args.kv_pool_bytes:
        cmd += ['--kv-pool-bytes', str(args.kv_pool_bytes)]
    if args.weight_dtype:
        cmd += ['--weight-dtype', args.weight_dtype]
    if args.kv_spill_bytes:
        cmd += ['--kv-spill-bytes', str(args.kv_spill_bytes)]
    if args.kv_cold_dir:
        cmd += ['--kv-cold-dir', args.kv_cold_dir]
    if args.tensor > 1:
        cmd += ['--tensor', str(args.tensor)]
    if getattr(args, 'stages', 1) > 1:
        cmd += ['--stages', str(args.stages)]
    if adapter_dir:
        cmd += ['--adapter-dir', adapter_dir,
                '--max-adapters', str(max(args.max_adapters,
                                          args.adapters))]
    if args.engine == 'continuous':
        cmd += ['--continuous-batching', '--num-slots',
                str(args.num_slots)]
    if args.no_prefix_caching:
        cmd += ['--no-prefix-caching']
    if args.speculative:
        cmd += ['--speculative', str(args.speculative)]
    if args.decode_chunk > 1:
        cmd += ['--decode-chunk', str(args.decode_chunk)]
    if args.prefill_chunk is not None:
        cmd += ['--prefill-chunk', str(args.prefill_chunk)]
    if args.prefill_budget is not None:
        cmd += ['--prefill-budget', str(args.prefill_budget)]
    if args.no_pipeline_decode:
        cmd += ['--no-pipeline-decode']
    if args.fault_plan:
        cmd += ['--fault-plan', args.fault_plan]
    if args.request_timeout is not None:
        cmd += ['--request-timeout', str(args.request_timeout)]
    if args.max_queue_requests is not None:
        cmd += ['--max-queue-requests', str(args.max_queue_requests)]
    if args.max_queue_tokens is not None:
        cmd += ['--max-queue-tokens', str(args.max_queue_tokens)]
    if args.hf:
        cmd += ['--hf', args.hf]
    if args.ckpt_dir:
        cmd += ['--ckpt-dir', args.ckpt_dir]
    if args.cpu:
        cmd += ['--cpu']
    return cmd


def _make_adapter_artifacts(args, out_dir: str) -> list:
    """Generate --adapters N random adapter artifacts for the bench
    model (deterministic: adapter i is seeded with i). Imports the
    model registry in-process only for the config geometry."""
    from skypilot_tpu.models import lora as lora_lib
    from skypilot_tpu.recipes.train_lm import _build_model
    model, _, _ = _build_model(args.model, args.max_total_len,
                               remat=False)
    spec = lora_lib.LoraSpec(rank=args.adapter_rank,
                             alpha=2.0 * args.adapter_rank)
    names = []
    for i in range(args.adapters):
        name = f'ad{i:03d}'
        params = lora_lib.random_adapter_params(i, model.config, spec)
        lora_lib.save_adapter(os.path.join(out_dir, name), params,
                              spec, base_model=args.model)
        names.append(name)
    return names


def _adapter_assignment(args, names: list) -> list:
    """Deterministic per-request adapter assignment. `uniform` draws
    each adapter equally; `zipf` draws adapter k with weight
    1/(k+1) — the few-hot-tenants regime that exercises the LRU
    (cold adapters keep evicting and reloading)."""
    rng = random.Random(1)
    if args.adapter_mix == 'uniform':
        return [names[rng.randrange(len(names))]
                for _ in range(args.requests)]
    weights = [1.0 / (k + 1) for k in range(len(names))]
    total = sum(weights)
    cum = []
    acc = 0.0
    for w in weights:
        acc += w / total
        cum.append(acc)
    out = []
    for _ in range(args.requests):
        r = rng.random()
        idx = next(i for i, c in enumerate(cum) if r <= c)
        out.append(names[idx])
    return out


def _fleet_prompts(args, vocab: int, rng) -> list:
    """The fleet workload: random short prompts, each carrying one of
    `--prefix-groups` distinct shared system prefixes (group = request
    index mod groups — deterministic, interleaved). Multiple groups
    are what separates the policies: under affinity each group pins to
    one replica (its pages cached once); under round-robin every
    replica pays and caches every group's pages."""
    prompts = [[rng.randrange(1, vocab)
                for _ in range(rng.randrange(4, 16))]
               for _ in range(args.requests)]
    if args.shared_prefix:
        groups = max(1, args.prefix_groups or 8)
        systems = [[rng.randrange(1, vocab)
                    for _ in range(args.shared_prefix)]
                   for _ in range(groups)]
        # Seeded-random group per request, NOT i % groups: a modulo
        # assignment correlates with round-robin's i % replicas and
        # accidentally pins groups under the control policy.
        prompts = [systems[rng.randrange(groups)] + p
                   for p in prompts]
    if args.long_prompt_frac > 0:
        # Unique (uncached) long prompts spread through the workload:
        # the compute-bound prefill traffic the disaggregated arm
        # moves off the decode pool.
        long_len = args.long_prompt_len or max(
            16, args.max_total_len - args.max_new_tokens - 2)
        n_long = int(round(args.long_prompt_frac * len(prompts)))
        for i in range(n_long):
            idx = (i * len(prompts)) // max(n_long, 1)
            prompts[idx] = [rng.randrange(1, vocab)
                            for _ in range(long_len)]
    return prompts


def _run_fleet_once(args, policy_name: str) -> dict:
    """One fleet run under one LB policy: spawn --replicas servers
    behind the replica-plane LB, drive the workload through it,
    report per-replica breakdown + affinity ratio."""
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import \
        load_balancing_policies  # noqa: F401 (registers policies)
    from skypilot_tpu.serve import service_spec as spec_lib
    from skypilot_tpu.serve.replica_plane import (FleetController,
                                                  ReplicaManager,
                                                  make_lb_server)
    from skypilot_tpu.serve.replica_plane import replica_manager as rm
    from skypilot_tpu.utils.registry import LB_POLICY_REGISTRY

    env = _server_env(args)
    if args.stub_replicas:
        factory = rm.stub_factory(
            extra_args=['--cache-pages', str(args.stub_cache_pages),
                        '--token-sleep-ms',
                        str(args.stub_token_sleep_ms),
                        '--prefill-ms-per-token',
                        str(args.stub_prefill_ms_per_token)],
            env=env)
    else:
        factory = rm.serve_lm_factory(_build_server_cmd(args),
                                      env=env)
    spec = spec_lib.SkyServiceSpec(min_replicas=args.replicas,
                                   max_replicas=args.replicas)
    autoscaler = autoscalers.EngineMetricsAutoscaler(spec)
    policy = LB_POLICY_REGISTRY.from_str(policy_name)()
    # Disaggregated arm: a prefill pool of --prefill-replicas behind
    # the LB's prompt-length threshold, handing KV chains to the
    # decode pool.
    disagg = args.prefill_replicas > 0
    prefill_autoscaler = None
    prefill_pool = None
    if disagg:
        from skypilot_tpu.serve.replica_plane import PrefillPool
        pspec = spec_lib.SkyServiceSpec(
            min_replicas=args.prefill_replicas,
            max_replicas=args.prefill_replicas)
        prefill_autoscaler = autoscalers.EngineMetricsAutoscaler(
            pspec)
        prefill_pool = PrefillPool()
    # --state-dir journals the bench fleet too (the per-policy
    # subdir keeps the A/B arms' journals separate): benches double
    # as adoption drills — SIGKILL the bench and the replicas can be
    # adopted or reaped by a serve_fleet pointed at the same dir.
    state_dir = (os.path.join(args.state_dir, policy_name)
                 if args.state_dir else None)
    # Generous scrape tolerance: on a saturated 1-core bench host a
    # slow /stats answer is load, not death — flapping NOT_READY
    # would make the fixed-size autoscaler spawn replacement
    # interpreters mid-run, which worsens the very contention that
    # slowed the scrape (a spawn spiral the 30s-timeout fleet
    # defaults are not tuned against).
    manager = ReplicaManager(factory, drain_grace_s=30.0,
                             scrape_timeout_s=20.0,
                             max_scrape_failures=1000,
                             state_dir=state_dir)
    controller = FleetController(
        manager, policy, autoscaler, interval_s=1.0,
        prefill_autoscaler=prefill_autoscaler,
        prefill_pool=prefill_pool)
    lb_port = _free_port()
    lb = make_lb_server(
        policy, lb_port, policy_name=policy_name, manager=manager,
        disagg_threshold=(args.disagg_prompt_threshold
                          if disagg else 0),
        prefill_pool=prefill_pool)
    lb_thread = threading.Thread(target=lb.serve_forever, daemon=True)
    lb_thread.start()
    url = f'http://127.0.0.1:{lb_port}'
    try:
        for _ in range(args.replicas):
            manager.spawn(role='decode' if disagg else '')
        for _ in range(args.prefill_replicas):
            manager.spawn(role='prefill')
        total = args.replicas + args.prefill_replicas
        if not controller.wait_ready(total, timeout_s=300):
            raise RuntimeError(
                f'fleet of {total} not ready within 300s')
        controller.tick()  # push roles/peers before traffic
        info = requests.get(url, timeout=10).json()  # via LB
        vocab = int(info['vocab_size'])

        rng = random.Random(0)
        prompts = _fleet_prompts(args, vocab, rng)
        if not args.stub_replicas:
            # Warm every replica's compile caches directly (through
            # the LB, affinity would warm only each prompt's target).
            warm = [min(prompts, key=len), max(prompts, key=len)]
            for view in manager.views():
                for p in warm:
                    for _ in range(2):
                        requests.post(
                            f'http://{view.endpoint}/generate',
                            json={'tokens': [p],
                                  'max_new_tokens': 2}, timeout=600)

        ticker = threading.Thread(target=controller.run, daemon=True)
        ticker.start()

        latencies = []
        itl_gaps = []    # SSE inter-token gaps across ALL requests
        errors = [0]
        shed = [0]
        lock = threading.Lock()
        queue = list(enumerate(prompts))

        def client() -> None:
            while True:
                with lock:
                    if not queue:
                        return
                    _idx, prompt = queue.pop()
                t0 = time.perf_counter()
                ttft = None
                last_tok_t = None
                gaps = []
                try:
                    with requests.post(f'{url}/generate', json={
                            'tokens': [prompt],
                            'max_new_tokens': args.max_new_tokens,
                            'stream': True}, timeout=600,
                            stream=True) as resp:
                        if resp.status_code == 429:
                            with lock:
                                shed[0] += 1
                            continue
                        if resp.status_code >= 500:
                            with lock:
                                errors[0] += 1
                            continue
                        for raw in resp.iter_lines():
                            if not raw.startswith(b'data: '):
                                continue
                            if b'"token"' in raw:
                                now = time.perf_counter()
                                if ttft is None:
                                    ttft = now - t0
                                if last_tok_t is not None:
                                    gaps.append(now - last_tok_t)
                                last_tok_t = now
                            if raw == b'data: [DONE]':
                                break
                except requests.RequestException:
                    with lock:
                        errors[0] += 1
                    continue
                total = time.perf_counter() - t0
                with lock:
                    latencies.append((ttft if ttft is not None
                                      else total, total))
                    itl_gaps.extend(gaps)

        start = time.perf_counter()
        threads = [threading.Thread(target=client)
                   for _ in range(args.concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start

        manager.scrape_once()  # final per-replica stats
        snap = lb.lb_metrics.snapshot()
        views = sorted(manager.views(), key=lambda v: v.replica_id)
        total_hits = sum(v.prefix_hits for v in views)
        total_misses = sum(v.prefix_misses for v in views)
        ttfts = sorted(l[0] for l in latencies)
        gaps_sorted = sorted(itl_gaps)
        handoffs = {'handoffs': 0, 'failures': 0, 'kv_imports': 0}
        # DECODE-pool engine-side ITL: token-commit gaps scraped from
        # the replicas themselves (stub /stats ships the raw recent
        # gaps) — client SSE timing rides TCP buffering and misses
        # ms-scale engine contention. This is the number the disagg
        # sweep's acceptance gate reads.
        engine_gaps = []
        for v in views:
            h = (v.last_stats or {}).get('handoff') or {}
            for k in handoffs:
                handoffs[k] += int(h.get(k, 0) or 0)
            if disagg and v.role == 'prefill':
                continue
            engine_gaps.extend(
                float(g) / 1000.0 for g in
                ((v.last_stats or {}).get('itl_gaps_ms') or []))
        engine_gaps.sort()

        return {
            'lb_policy': policy_name,
            'replicas': args.replicas,
            'prefill_replicas': args.prefill_replicas,
            'disagg_prompt_threshold': (args.disagg_prompt_threshold
                                        if disagg else 0),
            'long_prompt_frac': args.long_prompt_frac,
            'requests': len(latencies),
            'client_errors': errors[0],
            'shed_requests': shed[0],
            'req_per_sec': round(len(latencies) / elapsed, 2),
            'ttft_n_samples': len(ttfts),
            'p50_ttft_ms': pct_ms(ttfts, 0.50),
            'p95_ttft_ms': pct_ms(ttfts, 0.95),
            'p99_ttft_ms': pct_ms(ttfts, 0.99),
            'sse_itl_n_samples': len(gaps_sorted),
            'sse_itl_ms_p50': pct_ms(gaps_sorted, 0.50),
            'sse_itl_ms_p99': pct_ms(gaps_sorted, 0.99),
            'decode_itl_n_samples': len(engine_gaps),
            'decode_itl_ms_p50': pct_ms(engine_gaps, 0.50),
            'decode_itl_ms_p99': pct_ms(engine_gaps, 0.99),
            'affinity_hit_ratio': snap['affinity_hit_ratio'],
            'lb_routed': snap['routed'],
            'lb_retried': snap['retried'],
            'handoffs': handoffs,
            'fleet_prefix_hit_rate': round(
                total_hits / max(total_hits + total_misses, 1), 4),
            'per_replica': [{
                'replica_id': v.replica_id,
                'role': v.role,
                'routed': snap['routed_per_replica'].get(
                    v.endpoint, 0),
                'prefix_hits': v.prefix_hits,
                'prefix_misses': v.prefix_misses,
                'prefix_hit_rate': round(v.prefix_hit_rate, 4),
                'kv_spill_bytes': v.kv_spill_bytes,
                'kv_restored_pages': v.kv_restored_pages,
            } for v in views],
        }
    finally:
        controller.shutdown()
        lb.shutdown()


def run_fleet(args) -> dict:
    """The --replicas N mode: one run per policy (--ab-policies runs
    prefix_affinity AND round_robin over the identical workload — the
    committed BENCH_serve_fleet JSON)."""
    policies = (['prefix_affinity', 'round_robin']
                if args.ab_policies else [args.lb_policy])
    runs = {name: _run_fleet_once(args, name) for name in policies}
    if not args.ab_policies:
        return runs[args.lb_policy]
    return {
        'bench': 'serve_fleet',
        'engine': args.engine,
        'model': args.model,
        'replicas': args.replicas,
        'requests': args.requests,
        'concurrency': args.concurrency,
        'shared_prefix': args.shared_prefix,
        'prefix_groups': args.prefix_groups,
        'stub_replicas': bool(args.stub_replicas),
        'runs': runs,
    }


def _run_single(args, adapter_dir=None, assignment=None) -> dict:
    """One single-server run (the non-fleet mode), returning the JSON
    record. `adapter_dir` arms serve_lm's adapter registry;
    `assignment` (list of adapter names per request index, None
    entries = base) drives the multi-LoRA workload."""
    port = _free_port()
    cmd = _build_server_cmd(args, adapter_dir) + ['--port', str(port)]
    env = _server_env(args)
    server = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                              stderr=subprocess.STDOUT)
    url = f'http://127.0.0.1:{port}'
    try:
        deadline = time.time() + 300
        info = None
        while time.time() < deadline:
            try:
                info = requests.get(url, timeout=2).json()
                break
            except requests.RequestException:
                time.sleep(1)
                if server.poll() is not None:
                    raise RuntimeError('serve_lm died')
        if info is None:
            raise RuntimeError('serve_lm not ready within 300s')
        vocab = int(info['vocab_size'])

        rng = random.Random(0)
        if args.repetitive:
            # Structured prompts (repeated trigrams): the shape
            # prompt-lookup speculation exploits — code, templated
            # text, retrieval contexts.
            def rep_prompt():
                gram = [rng.randrange(1, vocab) for _ in range(3)]
                n = rng.randrange(4, 16)
                return (gram * ((n + 2) // 3))[:n]
            prompts = [rep_prompt() for _ in range(args.requests)]
        else:
            prompts = [[rng.randrange(1, vocab)
                        for _ in range(rng.randrange(4, 16))]
                       for _ in range(args.requests)]
        if args.long_prompt_frac > 0:
            # Long prompts leave room to generate the full
            # max_new_tokens below max_total_len (submit requires
            # prompt_len < max_total_len).
            long_len = max(16, args.max_total_len -
                           args.max_new_tokens - 2)
            n_long = int(round(args.long_prompt_frac * len(prompts)))
            # Deterministic spread through the workload (not a
            # front-loaded burst).
            for i in range(n_long):
                idx = (i * len(prompts)) // max(n_long, 1)
                prompts[idx] = [rng.randrange(1, vocab)
                                for _ in range(long_len)]
        if args.shared_prefix:
            # --prefix-groups G > 1: G distinct shared prefixes with
            # seeded-random assignment (the multi-session residency
            # regime the quant A/B measures — more pool pages keep
            # more groups' pages resident). Default 1 = the classic
            # one-system-prompt workload.
            groups = max(1, args.prefix_groups or 1)
            systems = [[rng.randrange(1, vocab)
                        for _ in range(args.shared_prefix)]
                       for _ in range(groups)]
            prompts = [systems[rng.randrange(groups)] + p
                       for p in prompts]
        # Warm the compile caches (prefill buckets + decode). With
        # prefix caching the SECOND pass over a prompt takes the
        # suffix-prefill path (different bucket shapes) — warm the
        # shortest and longest so the timed section measures serving,
        # not XLA compiles.
        warm = [prompts[0]]
        if args.shared_prefix or args.long_prompt_frac > 0:
            warm.append(min(prompts, key=len))
            warm.append(max(prompts, key=len))
        for p in warm:
            for _ in range(2):
                requests.post(f'{url}/generate', json={
                    'tokens': [p], 'max_new_tokens': 2}, timeout=600)
        # Streaming warm-up: in simple mode the first streamed request
        # builds the lazy stream engine + its compiles (the timed
        # section must measure serving, not XLA).
        requests.post(f'{url}/generate', json={
            'tokens': [prompts[0]], 'max_new_tokens': 2,
            'stream': True}, timeout=600)
        if assignment:
            # LoRA-variant traces compile on the first adapter lane
            # (shared decode + prefill); one warm request covers them.
            requests.post(f'{url}/generate', json={
                'tokens': [prompts[0]], 'max_new_tokens': 2,
                'stream': True, 'model': assignment[0]}, timeout=600)

        # Window baseline for the engine's CUMULATIVE counters
        # (decode_stall_s, prefill_chunks_run, tokens_committed):
        # deltas over the timed section become honest rates — the
        # lifetime values fold warm-up compiles into the quotient.
        try:
            stats0 = requests.get(f'{url}/stats', timeout=30).json()
        except requests.RequestException:
            stats0 = {}

        latencies = []
        itl_gaps = []    # inter-token gaps across ALL requests (s)
        shed = [0]       # client-observed 429s (admission control)
        adapter_counts: dict = {}
        lock = threading.Lock()
        queue = list(enumerate(prompts))

        def client() -> None:
            while True:
                with lock:
                    if not queue:
                        return
                    idx, prompt = queue.pop()
                body = {'tokens': [prompt],
                        'max_new_tokens': args.max_new_tokens,
                        'stream': True}
                if assignment and assignment[idx] is not None:
                    body['model'] = assignment[idx]
                t0 = time.perf_counter()
                # REAL TTFT + ITL: stream the request (SSE), stamp the
                # first token frame and every gap between consecutive
                # token frames — one request measures TTFT, ITL, and
                # completion latency, exactly what a streaming client
                # experiences.
                ttft = None
                last_tok_t = None
                gaps = []
                with requests.post(f'{url}/generate', json=body,
                                   timeout=600, stream=True) as resp:
                    if resp.status_code == 429:
                        # Shed by admission control: count it and move
                        # on (a production client would honor
                        # Retry-After; the bench measures degradation,
                        # not retries).
                        with lock:
                            shed[0] += 1
                        continue
                    resp.raise_for_status()
                    for raw in resp.iter_lines():
                        if not raw.startswith(b'data: '):
                            continue
                        if b'"token"' in raw:
                            now = time.perf_counter()
                            if ttft is None:
                                ttft = now - t0
                            if last_tok_t is not None:
                                gaps.append(now - last_tok_t)
                            last_tok_t = now
                        if raw == b'data: [DONE]':
                            break
                total = time.perf_counter() - t0
                with lock:
                    latencies.append((ttft if ttft is not None
                                      else total, total))
                    itl_gaps.extend(gaps)
                    name = (assignment[idx] if assignment else None) \
                        or '<base>'
                    adapter_counts[name] = \
                        adapter_counts.get(name, 0) + 1

        start = time.perf_counter()
        threads = [threading.Thread(target=client)
                   for _ in range(args.concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start

        ttfts = sorted(l[0] for l in latencies)
        gaps = sorted(itl_gaps)
        # Server-side ITL percentiles (/stats): gaps measured at the
        # engine's token COMMIT, the signal chunked prefill targets —
        # client-side SSE gap timing rides TCP flush batching and
        # client GIL scheduling, which can swamp ms-scale effects.
        stats = requests.get(f'{url}/stats', timeout=30).json()
        serving = stats['serving']

        record = {
            'engine': args.engine,
            'speculative': args.speculative,
            'decode_chunk': args.decode_chunk,
            'prefill_chunk': args.prefill_chunk,
            'prefill_budget': args.prefill_budget,
            'pipeline_decode': not args.no_pipeline_decode,
            'shared_prefix': args.shared_prefix,
            'long_prompt_frac': args.long_prompt_frac,
            'prefix_caching': not args.no_prefix_caching,
            'model': info['model'],   # server-reported (handles --hf)
            'requests': len(latencies),
            'concurrency': args.concurrency,
            # Quantized-serving + tensor-parallel arms: storage
            # formats, the pool geometry the byte budget bought, and
            # req/s normalized per chip (the ROADMAP item-1 scaling
            # scoreboard — on CPU a "chip" is a virtual host device).
            'kv_dtype': (stats.get('storage') or {}).get('kv_dtype',
                                                         'bf16'),
            'weight_dtype': (stats.get('storage') or {}).get(
                'weight_dtype', 'bf16'),
            'weight_bytes': (stats.get('storage') or {}).get(
                'weight_bytes'),
            'kv_pages_total': (stats.get('page_pool') or {}).get(
                'total'),
            'kv_pool_bytes': (stats.get('page_pool') or {}).get(
                'pool_bytes'),
            # Sharded-pool geometry (PR 15): chips in the mesh, how
            # many ways the pool's kv-heads axis shards, and the
            # per-chip resident bytes (--kv-pool-bytes budgets the
            # LATTER — N sharded chips hold ~Nx kv_pages_total).
            'mesh_devices': (stats.get('storage') or {}).get(
                'mesh_devices'),
            'kv_shard_ways': (stats.get('page_pool') or {}).get(
                'shard_ways'),
            'kv_pool_bytes_per_device': (stats.get('page_pool')
                                         or {}).get(
                'pool_bytes_per_device'),
            # Pipeline-parallel serving (PR 19): per-stage pool split
            # (each stage owns only its layer range's bytes) and the
            # engine's closed-form (S-1)/(M+S-1) bubble of the last
            # prefill burst.
            'kv_pool_stages': (stats.get('page_pool') or {}).get(
                'stages'),
            'pipeline_stages': stats.get('pipeline_stages'),
            'prefill_bubble_fraction': stats.get(
                'prefill_bubble_fraction'),
            'prefix_hit_rate': (stats.get('prefix_cache') or {}).get(
                'hit_rate'),
            'prefix_evictions': (stats.get('prefix_cache') or {}).get(
                'evictions'),
            # Page-pressure preemptions: >0 means the pool could NOT
            # sustain the offered concurrency at this byte budget —
            # the "int8 sustains slots bf16 cannot" signal.
            'preemptions': stats.get('preemptions'),
            # Tiered cache: the spill tier's accounting (None when
            # the server runs without --kv-spill-bytes).
            'kv_spill': stats.get('kv_spill'),
            'tensor': args.tensor,
            'stages': max(getattr(args, 'stages', 1), 1),
            'req_per_sec': round(len(latencies) / elapsed, 2),
            # "chips" = the full (stage, tensor) mesh: per-chip
            # numbers stay comparable between TP-only and TPxPP arms
            # at equal device count.
            'per_chip_req_per_sec': round(
                len(latencies) / elapsed /
                (max(args.tensor, 1) *
                 max(getattr(args, 'stages', 1), 1)), 2),
            'ttft_n_samples': len(ttfts),
            'p50_ttft_ms': pct_ms(ttfts, 0.50),
            'p95_ttft_ms': pct_ms(ttfts, 0.95),
            'p99_ttft_ms': pct_ms(ttfts, 0.99),
            'itl_ms_n': serving.get('itl_ms_n'),
            'itl_ms_p50': serving.get('itl_ms_p50'),
            'itl_ms_p99': serving.get('itl_ms_p99'),
            'sse_itl_n_samples': len(gaps),
            'sse_itl_ms_p50': pct_ms(gaps, 0.50),
            'sse_itl_ms_p99': pct_ms(gaps, 0.99),
            # Robustness plane: degradation under --fault-plan /
            # admission control is A/B-able from the same JSON line.
            'fault_plan': bool(args.fault_plan),
            'shed_requests': shed[0],
            'server_requests_shed': serving.get('requests_shed'),
            'server_deadline_exceeded':
                serving.get('deadline_exceeded'),
            'engine_restarts': stats.get('engine_restarts'),
        }
        d_tokens = ((stats.get('tokens_committed') or 0) -
                    (stats0.get('tokens_committed') or 0))
        if stats.get('engine') == 'continuous':
            # Window-normalized scheduler health: stall seconds per
            # wall second / per generated token, and chunked-prefill
            # cadence — comparable across runs of different lengths.
            d_stall = ((stats.get('decode_stall_s') or 0.0) -
                       (stats0.get('decode_stall_s') or 0.0))
            d_chunks = ((stats.get('prefill_chunks_run') or 0) -
                        (stats0.get('prefill_chunks_run') or 0))
            record['decode_stall_s_window'] = round(d_stall, 4)
            record['decode_stall_s_per_s'] = round(
                d_stall / elapsed, 5)
            record['decode_stall_ms_per_token'] = round(
                1000.0 * d_stall / max(d_tokens, 1), 4)
            record['prefill_chunks_per_s'] = round(
                d_chunks / elapsed, 3)
        bpt = stats.get('attention_bytes_per_token')
        if bpt:
            # Roofline scoreboard: achieved per-chip tokens/s against
            # the analytic HBM bytes/token model the server exports
            # (ops/pallas_paged.bytes_per_token_model via /stats).
            # fraction_of_hbm_peak ~= how much of the memory roof the
            # decode loop actually sustains; on CPU it is a sanity
            # denominator, on TPU the tuning target.
            # bytes_per_token_model is already per-chip under stage
            # and tensor splits (each chip walks only its own stage's
            # layers / kv-head shard), so dividing tokens/s by the
            # full chip count keeps the roofline product honest.
            tokens_per_s = d_tokens / elapsed
            per_chip = tokens_per_s / (
                max(args.tensor, 1) *
                max(getattr(args, 'stages', 1), 1))
            bytes_per_s = per_chip * bpt['total_bytes_per_token']
            record['roofline'] = {
                'attention_impl': stats.get('attention_impl'),
                'bytes_per_token_model': bpt,
                'tokens_per_s': round(tokens_per_s, 2),
                'per_chip_tokens_per_s': round(per_chip, 2),
                'modeled_hbm_bytes_per_s_per_chip': round(
                    bytes_per_s, 1),
                'hbm_peak_gbps': args.hbm_peak_gbps,
                'fraction_of_hbm_peak': round(
                    bytes_per_s / (args.hbm_peak_gbps * 1e9), 8),
            }
        if adapter_dir:
            # Per-adapter req/s (client-side) + the registry's own
            # residency/eviction accounting (server-side).
            server_ad = stats.get('adapters') or {}
            record['adapters'] = {
                'n': args.adapters,
                'mix': args.adapter_mix if assignment else 'base-only',
                'rank': args.adapter_rank,
                'per_adapter': {
                    name: {'requests': n,
                           'req_per_sec': round(n / elapsed, 3)}
                    for name, n in sorted(adapter_counts.items())},
                'server_loads': server_ad.get('loads'),
                'server_evictions': server_ad.get('evictions'),
                'server_load_failures': server_ad.get('load_failures'),
                'server_requests': server_ad.get('requests'),
                'loaded_at_end': server_ad.get('loaded'),
                'bytes_per_adapter': server_ad.get(
                    'bytes_per_adapter'),
            }
        return record
    finally:
        server.terminate()
        try:
            server.wait(timeout=10)
        except subprocess.TimeoutExpired:
            server.kill()


def _with(args, **over) -> argparse.Namespace:
    """A shallow copy of the parsed args with fields overridden (the
    A/B arms vary one knob over an otherwise identical workload)."""
    import copy
    arm = copy.copy(args)
    for key, val in over.items():
        setattr(arm, key, val)
    return arm


def run_quant_ab(args) -> dict:
    """The quantized-serving A/B (the committed BENCH_quant record):
    bf16 KV vs int8 KV at the SAME --kv-pool-bytes (int8 buys ~2x
    the pages — more slots / prefix residency per HBM byte), plus an
    int8-KV + int8-weights arm. Identical workload per arm."""
    runs = {
        'kv_bf16': _run_single(_with(args, kv_dtype='bf16',
                                     weight_dtype=None)),
        'kv_int8': _run_single(_with(args, kv_dtype='int8',
                                     weight_dtype=None)),
        'kv_int8_w_int8': _run_single(_with(args, kv_dtype='int8',
                                            weight_dtype='int8')),
    }
    base, q = runs['kv_bf16'], runs['kv_int8']
    return {
        'bench': 'serve_quant',
        'engine': args.engine,
        'model': args.model,
        'kv_pool_bytes': args.kv_pool_bytes,
        'requests': args.requests,
        'concurrency': args.concurrency,
        'num_slots': args.num_slots,
        'shared_prefix': args.shared_prefix,
        'prefix_groups': max(1, args.prefix_groups or 1),
        # Same pool bytes -> int8 holds ~2x the pages: the
        # slots/residency headline (>= 1.8 is the acceptance gate).
        'kv_pages_ratio_int8_vs_bf16': round(
            q['kv_pages_total'] / max(base['kv_pages_total'], 1), 3),
        'req_per_sec_ratio_int8_vs_bf16': round(
            q['req_per_sec'] / max(base['req_per_sec'], 1e-9), 3),
        'runs': runs,
    }


def run_tensor_ab(args) -> dict:
    """--tensor 1 vs --tensor N over the identical workload: the
    per-chip decode-throughput scaling record (ROADMAP item 1's
    still-missing serve_bench deliverable; CPU runs fake the chips
    with XLA host devices).

    With --kv-pool-bytes set the A/B grows a POOL-CAPACITY axis
    (PR 15): the flag is per-chip, so both arms spend the same HBM
    per chip, and the sharded-pool arm should report ~Nx the TOTAL
    pages — the headline `pool_pages_ratio` — with fewer
    page-pressure preemptions and better prefix-cache residency at
    the same offered load."""
    n = max(2, args.tensor)
    runs = {
        'tensor_1': _run_single(_with(args, tensor=1)),
        f'tensor_{n}': _run_single(_with(args, tensor=n)),
    }
    base, tp = runs['tensor_1'], runs[f'tensor_{n}']
    out = {
        'bench': 'serve_tensor',
        'engine': args.engine,
        'model': args.model,
        'tensor': n,
        'requests': args.requests,
        'concurrency': args.concurrency,
        'kv_dtype': args.kv_dtype or 'bf16',
        'weight_dtype': args.weight_dtype or 'bf16',
        'per_chip_ratio': round(
            tp['per_chip_req_per_sec'] /
            max(base['per_chip_req_per_sec'], 1e-9), 3),
        'runs': runs,
    }
    if args.kv_pool_bytes:
        out['kv_pool_bytes_per_chip'] = args.kv_pool_bytes
        out['pool_pages_ratio'] = round(
            (tp['kv_pages_total'] or 0) /
            max(base['kv_pages_total'] or 0, 1), 3)
        out['pool_capacity'] = {
            arm: {'kv_pages_total': rec['kv_pages_total'],
                  'kv_shard_ways': rec['kv_shard_ways'],
                  'kv_pool_bytes_per_device':
                      rec['kv_pool_bytes_per_device'],
                  'preemptions': rec['preemptions'],
                  'prefix_hit_rate': rec['prefix_hit_rate']}
            for arm, rec in runs.items()}
    return out


def run_pp_ab(args) -> dict:
    """TP-only vs TP x PP at EQUAL chip count over the identical
    greedy workload (the committed BENCH_tp_pp record): with
    --tensor T --stages S the arms are tensor=T*S/stages=1 and
    tensor=T/stages=S on the same T*S virtual chips. The staged arm
    splits the KV pool by LAYER RANGE on top of the kv-heads shard —
    --kv-pool-bytes is per chip, so at fixed per-chip HBM the staged
    pool holds ~S x the pages per shard group (`pool_pages_ratio`)
    — while the pipelined chunk stream prices prefill at the
    closed-form (S-1)/(M+S-1) fill/drain bubble and the S-deep
    decode ring keeps p99 ITL within a small factor of TP-only
    (`decode_itl_p99_ratio`; the acceptance gate is <= 1.25)."""
    s = max(2, args.stages)
    t = max(1, args.tensor)
    chips = s * t
    tp_arm, pp_arm = f'tp{chips}', f'tp{t}_pp{s}'
    runs = {
        tp_arm: _run_single(_with(args, tensor=chips, stages=1)),
        pp_arm: _run_single(_with(args, tensor=t, stages=s)),
    }
    base, pp = runs[tp_arm], runs[pp_arm]
    from skypilot_tpu.parallel.pipeline_schedule import (
        make_inference_schedule)
    base_roof = base.get('roofline') or {}
    pp_roof = pp.get('roofline') or {}
    out = {
        'bench': 'serve_tp_pp',
        'engine': args.engine,
        'model': args.model,
        'chips': chips,
        'tensor': t,
        'stages': s,
        'requests': args.requests,
        'concurrency': args.concurrency,
        'kv_dtype': args.kv_dtype or 'bf16',
        # Headlines: per-chip decode throughput and tail ITL of the
        # staged arm relative to TP-only at the same chip count.
        'per_chip_req_ratio': round(
            pp['per_chip_req_per_sec'] /
            max(base['per_chip_req_per_sec'], 1e-9), 3),
        'per_chip_decode_tokens_ratio': round(
            (pp_roof.get('per_chip_tokens_per_s') or 0.0) /
            max(base_roof.get('per_chip_tokens_per_s') or 0.0, 1e-9),
            3),
        'decode_itl_p99_ratio': round(
            (pp['itl_ms_p99'] or 0.0) /
            max(base['itl_ms_p99'] or 0.0, 1e-9), 3),
        # The staged arm's measured last-burst bubble plus the
        # analytic (S-1)/(M+S-1) table it must sit in — read from
        # the schedule object, not re-derived here.
        'prefill_bubble_fraction': pp['prefill_bubble_fraction'],
        'prefill_bubble_closed_form': {
            f'microbatches_{m}': round(
                make_inference_schedule(s, m).bubble_fraction, 6)
            for m in (1, 2, 4, 8)},
        'runs': runs,
    }
    if args.kv_pool_bytes:
        out['kv_pool_bytes_per_chip'] = args.kv_pool_bytes
        out['pool_pages_ratio'] = round(
            (pp['kv_pages_total'] or 0) /
            max(base['kv_pages_total'] or 0, 1), 3)
        out['pool_capacity'] = {
            arm: {'kv_pages_total': rec['kv_pages_total'],
                  'kv_shard_ways': rec['kv_shard_ways'],
                  'kv_pool_bytes_per_device':
                      rec['kv_pool_bytes_per_device'],
                  'kv_pool_stages': rec['kv_pool_stages'],
                  'preemptions': rec['preemptions'],
                  'prefix_hit_rate': rec['prefix_hit_rate']}
            for arm, rec in runs.items()}
    return out


def run_disagg_ab(args) -> dict:
    """The disaggregation scoreboard (the committed BENCH_disagg
    record's `sweep` half): a long-prompt-fraction sweep over TWO
    stub fleets of equal total size — UNIFIED (every replica
    prefills its own prompts; long prefills hold the engine lock and
    stretch co-resident streams' inter-token gaps) vs DISAGGREGATED
    (long prompts route to a prefill pool that hands the KV chain to
    the decode pool; decode replicas never pay the prefill). Stub
    replicas make the engine-contention model deterministic on a
    1-core bench host; the real-engine bit-identity of the handoff
    and spill paths is pinned by tier-1 (test_kv_transfer.py)."""
    total = args.replicas + max(args.prefill_replicas, 1)
    fracs = [0.0, 0.25, 0.5]
    sweep = {'unified': {}, 'disagg': {}}
    for frac in fracs:
        unified = _run_fleet_once(
            _with(args, long_prompt_frac=frac, prefill_replicas=0,
                  replicas=total),
            args.lb_policy)
        disagg = _run_fleet_once(
            _with(args, long_prompt_frac=frac,
                  prefill_replicas=max(args.prefill_replicas, 1),
                  replicas=total - max(args.prefill_replicas, 1)),
            args.lb_policy)
        sweep['unified'][str(frac)] = unified
        sweep['disagg'][str(frac)] = disagg

    def ratio(runs):
        base = runs['0.0']['decode_itl_ms_p99'] or 1e-9
        return {frac: round((runs[frac]['decode_itl_ms_p99'] or 0.0)
                            / base, 3)
                for frac in runs}

    return {
        'bench': 'serve_disagg_sweep',
        'stub_replicas': True,
        'total_replicas': total,
        'prefill_replicas': max(args.prefill_replicas, 1),
        'disagg_prompt_threshold': args.disagg_prompt_threshold,
        'long_prompt_len': args.long_prompt_len,
        'long_prompt_fracs': fracs,
        'requests': args.requests,
        'concurrency': args.concurrency,
        'stub_token_sleep_ms': args.stub_token_sleep_ms,
        'stub_prefill_ms_per_token': args.stub_prefill_ms_per_token,
        # p99 ITL at each fraction relative to that arm's frac=0
        # value: the acceptance gate is disagg <= 1.25 at every
        # fraction while unified degrades.
        'p99_itl_vs_frac0': {'unified': ratio(sweep['unified']),
                             'disagg': ratio(sweep['disagg'])},
        'sweep': sweep,
    }


def _storm_expected_tokens(seed: int, prompt_len: int,
                           max_new: int) -> list:
    """The stub's deterministic token row for a prompt of
    `prompt_len` under a FLEET-SHARED seed: the unmigrated control
    an evacuated stream must match bit-for-bit (stub.py's formula —
    tokens depend only on seed, prompt length, and position, never
    on which replica generates them)."""
    return [(seed * 1000003 + prompt_len * 31 + j) % 50000
            for j in range(max_new)]


def _run_storm_once(args, arm: str) -> dict:
    """One storm arm over a stub fleet: `control` (no fault plan),
    `migrate` (zone storm; preempted replicas evacuate KV chains to
    survivors inside the grace window), or `replay` (zone storm with
    --no-migrate: preemption aborts the replica mid-stream and the
    client retries from the full prompt). All replicas share one
    seed so a migrated continuation is bit-comparable against the
    client-side expected row."""
    from skypilot_tpu.serve import autoscalers
    from skypilot_tpu.serve import \
        load_balancing_policies  # noqa: F401 (registers policies)
    from skypilot_tpu.serve import service_spec as spec_lib
    from skypilot_tpu.serve.replica_plane import (FleetController,
                                                  ReplicaManager,
                                                  make_lb_server)
    from skypilot_tpu.serve.replica_plane import lb as lb_mod
    from skypilot_tpu.serve.replica_plane import replica_manager as rm
    from skypilot_tpu.utils.registry import LB_POLICY_REGISTRY

    env = _server_env(args)
    if arm != 'control':
        # Stubs take no --fault-plan flag; the plan arms from the
        # child environment at import. The bench process itself
        # never sees it (os.environ is untouched).
        env['STPU_FAULT_PLAN'] = args.fault_plan
    extra = ['--cache-pages', str(args.stub_cache_pages),
             '--token-sleep-ms', str(args.stub_token_sleep_ms),
             # Fleet-shared seed (last --seed wins over the
             # factory's per-replica one): bit-identity across
             # migration is checkable against a closed form.
             '--seed', str(args.storm_seed)]
    if arm == 'replay':
        extra += ['--no-migrate']
    factory = rm.stub_factory(extra_args=extra, env=env)
    spec = spec_lib.SkyServiceSpec(min_replicas=args.replicas,
                                   max_replicas=args.replicas)
    autoscaler = autoscalers.EngineMetricsAutoscaler(spec)
    policy = LB_POLICY_REGISTRY.from_str(args.lb_policy)()
    # Preempted replicas are FAILED and then forgotten by the next
    # controller tick (terminal views are removed) — count them at
    # the lifecycle event, not from the end-of-run view list.
    preempted = [0]

    def on_event(name: str, view) -> None:
        if name == 'dead' and getattr(view, 'zone', '') == \
                args.storm_zone:
            preempted[0] += 1

    manager = ReplicaManager(factory, drain_grace_s=30.0,
                             scrape_timeout_s=20.0,
                             max_scrape_failures=1000,
                             on_event=on_event)
    # Tight tick: a preempted replica must leave the routing set
    # (and its replacement arrive) within a fraction of the storm.
    controller = FleetController(manager, policy, autoscaler,
                                 interval_s=0.5)
    lb_port = _free_port()
    lb = make_lb_server(policy, lb_port, policy_name=args.lb_policy,
                        manager=manager)
    lb_thread = threading.Thread(target=lb.serve_forever, daemon=True)
    lb_thread.start()
    url = f'http://127.0.0.1:{lb_port}'
    try:
        # First --storm-spot replicas carry the storm zone; the rest
        # are the on-demand survivors chains evacuate to.
        for i in range(args.replicas):
            zone = args.storm_zone if i < args.storm_spot else ''
            manager.spawn(zone=zone)
        if not controller.wait_ready(args.replicas, timeout_s=120):
            raise RuntimeError(
                f'storm fleet of {args.replicas} not ready')
        controller.tick()  # push peer sets before traffic
        ticker = threading.Thread(target=controller.run, daemon=True)
        ticker.start()

        rng = random.Random(0)
        prompts = [[rng.randrange(1, 50000)
                    for _ in range(rng.randrange(4, 16))]
                   for _ in range(args.requests)]
        latencies = []
        itl_gaps = []
        errors = [0]        # final (unrecovered) 5xx / transport
        retries = [0]       # replay-arm full-prompt resubmissions
        recomputed = [0]    # client-visible recompute: prompt +
        #                     already-received tokens per retry
        mismatches = [0]    # completed rows != closed-form control
        shed = [0]
        lock = threading.Lock()
        queue = list(enumerate(prompts))

        def client() -> None:
            while True:
                with lock:
                    if not queue:
                        return
                    _idx, prompt = queue.pop()
                expected = _storm_expected_tokens(
                    args.storm_seed, len(prompt),
                    args.max_new_tokens)
                t0 = time.perf_counter()
                attempt = 0
                while True:
                    attempt += 1
                    ttft = None
                    last_t = None
                    gaps = []
                    toks = []
                    failed = False
                    try:
                        with requests.post(f'{url}/generate', json={
                                'tokens': [prompt],
                                'max_new_tokens':
                                    args.max_new_tokens,
                                'stream': True}, timeout=600,
                                stream=True) as resp:
                            if resp.status_code == 429:
                                with lock:
                                    shed[0] += 1
                                break
                            if resp.status_code >= 500:
                                failed = True
                            else:
                                done = False
                                # chunk_size=1: SSE frames are a
                                # few dozen bytes; default chunking
                                # batches whole bursts into one
                                # read and flattens every gap to 0.
                                for raw in resp.iter_lines(
                                        chunk_size=1):
                                    if not raw.startswith(b'data: '):
                                        continue
                                    if raw == b'data: [DONE]':
                                        done = True
                                        break
                                    frame = json.loads(raw[6:])
                                    if 'token' in frame:
                                        now = time.perf_counter()
                                        if ttft is None:
                                            ttft = now - t0
                                        if last_t is not None:
                                            gaps.append(now - last_t)
                                        last_t = now
                                        toks.append(
                                            int(frame['token']))
                                if not done:
                                    # Connection died mid-stream
                                    # (preempted replica).
                                    failed = True
                    except requests.RequestException:
                        failed = True
                    if not failed:
                        total = time.perf_counter() - t0
                        with lock:
                            latencies.append(
                                (ttft if ttft is not None else total,
                                 total))
                            itl_gaps.extend(gaps)
                            if toks != expected:
                                mismatches[0] += 1
                        break
                    # A failed attempt restarts from the raw prompt:
                    # the server must re-prefill it AND regenerate
                    # every token the client already held — the
                    # replay arm's whole cost model.
                    with lock:
                        recomputed[0] += len(prompt) + len(toks)
                    if attempt > 5:
                        with lock:
                            errors[0] += 1
                        break
                    with lock:
                        retries[0] += 1
                    time.sleep(0.5)

        start = time.perf_counter()
        threads = [threading.Thread(target=client)
                   for _ in range(args.concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start

        manager.scrape_once()
        views = sorted(manager.views(), key=lambda v: v.replica_id)
        migration = lb_mod.merge_migration_stats(views)
        # The sender's evacuation counters die with its process (it
        # exits after the grace window, before a final scrape);
        # receivers' migrations_in is the durable session count.
        sessions_evac = max(
            int(migration.get('sessions_evacuated', 0) or 0),
            int(migration.get('migrations_in', 0) or 0))
        server_recomputed = int(migration.get('tokens_recomputed', 0)
                                or 0)
        # Per-disrupted-session recompute: the migrate arm pays the
        # sub-page remainder the chain keys could not cover; the
        # replay arm pays the full prompt + lost tokens per retry.
        if arm == 'replay':
            per_session = (recomputed[0] / retries[0]
                           if retries[0] else 0.0)
        else:
            per_session = (server_recomputed / sessions_evac
                           if sessions_evac else 0.0)
        ttfts = sorted(l[0] for l in latencies)
        gaps_sorted = sorted(itl_gaps)
        return {
            'arm': arm,
            'replicas': args.replicas,
            'spot_replicas': args.storm_spot,
            'storm_zone': args.storm_zone,
            'requests': len(latencies),
            'client_errors': errors[0],
            'client_retries': retries[0],
            'shed_requests': shed[0],
            'token_mismatches': mismatches[0],
            'replicas_preempted': preempted[0],
            'sessions_migrated': sessions_evac,
            'req_per_sec': round(len(latencies) / elapsed, 2),
            'p50_ttft_ms': pct_ms(ttfts, 0.50),
            'p99_ttft_ms': pct_ms(ttfts, 0.99),
            'sse_itl_ms_p50': pct_ms(gaps_sorted, 0.50),
            'sse_itl_ms_p99': pct_ms(gaps_sorted, 0.99),
            'migration': migration,
            'tokens_recomputed_client': recomputed[0],
            'tokens_recomputed_server': server_recomputed,
            'tokens_recomputed_per_preempted_session': round(
                per_session, 2),
        }
    finally:
        controller.shutdown()
        lb.shutdown()


def run_storm_ab(args) -> dict:
    """The spot-storm A/B (the committed BENCH_migrate record):
    the IDENTICAL workload through three stub fleets — no storm
    (control), a zone storm answered by live KV-chain migration,
    and the same storm with migration disabled (full replay from
    the prompt). Headlines: tokens recomputed per preempted
    session (~0 for migration vs prompt+lost-tokens for replay),
    zero client 5xx in the migration arm, and every completed row
    bit-identical to the closed-form unmigrated control."""
    runs = {
        'control': _run_storm_once(args, 'control'),
        'migrate': _run_storm_once(args, 'migrate'),
        'replay': _run_storm_once(args, 'replay'),
    }
    mig, rep = runs['migrate'], runs['replay']
    return {
        'bench': 'serve_storm',
        'stub_replicas': True,
        'replicas': args.replicas,
        'spot_replicas': args.storm_spot,
        'storm_zone': args.storm_zone,
        'fault_plan': args.fault_plan,
        'requests': args.requests,
        'concurrency': args.concurrency,
        'max_new_tokens': args.max_new_tokens,
        'stub_token_sleep_ms': args.stub_token_sleep_ms,
        'storm_seed': args.storm_seed,
        'migrate_zero_5xx': mig['client_errors'] == 0,
        'migrate_outputs_bit_identical':
            mig['token_mismatches'] == 0,
        'tokens_recomputed_per_preempted_session': {
            'migrate': mig['tokens_recomputed_per_preempted_session'],
            'replay': rep['tokens_recomputed_per_preempted_session'],
        },
        'p99_itl_ms': {name: r['sse_itl_ms_p99']
                       for name, r in runs.items()},
        'runs': runs,
    }


def run_spill_ab(args) -> dict:
    """The tiered-cache A/B (the committed BENCH_disagg record's
    `spill` half): the SAME multi-session workload against a
    pool-pressured llama-tiny server with and without the host-RAM
    spill tier. Without it, every pool-pressure eviction recomputes
    the prefix on the next hit; with it, the pages restore
    bit-identically (tier-1 pins the bit-identity) — the prefix hit
    rate must be strictly higher."""
    runs = {
        'no_spill': _run_single(_with(args, kv_spill_bytes=0)),
        'spill': _run_single(_with(
            args,
            kv_spill_bytes=args.kv_spill_bytes or 256 * 1024 * 1024)),
    }
    base = runs['no_spill']
    tier = runs['spill']
    return {
        'bench': 'serve_spill',
        'engine': args.engine,
        'model': args.model,
        'kv_pool_bytes': args.kv_pool_bytes,
        'kv_spill_bytes': (args.kv_spill_bytes or
                           256 * 1024 * 1024),
        'requests': args.requests,
        'concurrency': args.concurrency,
        'shared_prefix': args.shared_prefix,
        'prefix_groups': max(1, args.prefix_groups or 1),
        'prefix_hit_rate_no_spill': base.get('prefix_hit_rate'),
        'prefix_hit_rate_spill': tier.get('prefix_hit_rate'),
        'evictions_no_spill': base.get('prefix_evictions'),
        'restored_pages': ((tier.get('kv_spill') or {})
                           .get('restored_pages')),
        'runs': runs,
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--engine', choices=['continuous', 'simple'],
                        default='continuous')
    parser.add_argument('--model', default='llama-tiny')
    parser.add_argument('--requests', type=int, default=64)
    parser.add_argument('--concurrency', type=int, default=8)
    parser.add_argument('--max-total-len', type=int, default=64)
    parser.add_argument('--max-new-tokens', type=int, default=24)
    parser.add_argument('--num-slots', type=int, default=8)
    parser.add_argument('--speculative', type=int, default=0,
                        metavar='K', help='prompt-lookup speculation '
                        '(works with both engines)')
    parser.add_argument('--decode-chunk', type=int, default=1,
                        metavar='N',
                        help='continuous engine: N decode steps per '
                             'dispatch (dispatch-overhead '
                             'amortization)')
    parser.add_argument('--long-prompt-frac', type=float, default=0.0,
                        metavar='F',
                        help='fraction of requests carrying a LONG '
                             'prompt (near max-total-len minus the '
                             'generation budget) mixed into the short '
                             'workload — the regime where whole-'
                             'prompt prefill stalls inter-token '
                             'latency and chunked prefill should not')
    parser.add_argument('--prefill-chunk', type=int, default=None,
                        metavar='C',
                        help='forwarded to serve_lm --prefill-chunk '
                             '(0 disables chunked prefill for A/B '
                             'runs; default: server default)')
    parser.add_argument('--prefill-budget', type=int, default=None,
                        metavar='T',
                        help='forwarded to serve_lm --prefill-budget')
    parser.add_argument('--no-pipeline-decode', action='store_true',
                        help='forwarded to serve_lm (disables '
                             'host/device decode pipelining)')
    parser.add_argument('--fault-plan', default=None, metavar='JSON',
                        help='forwarded to serve_lm --fault-plan '
                             '(inline JSON or a file path): run the '
                             'workload under injected faults and A/B '
                             'the JSON line against a clean run')
    parser.add_argument('--request-timeout', type=float, default=None,
                        help='forwarded to serve_lm '
                             '--request-timeout')
    parser.add_argument('--max-queue-requests', type=int, default=None,
                        help='forwarded to serve_lm '
                             '--max-queue-requests (shed + 429 when '
                             'saturated; shed count lands in the '
                             'JSON line)')
    parser.add_argument('--max-queue-tokens', type=int, default=None,
                        help='forwarded to serve_lm '
                             '--max-queue-tokens')
    parser.add_argument('--replicas', type=int, default=0,
                        metavar='N',
                        help='multi-replica mode: N serve_lm '
                             'processes behind the replica-plane LB '
                             '(serve/replica_plane/); the JSON line '
                             'gains a per-replica breakdown + '
                             'affinity hit ratio. 0 = single server')
    parser.add_argument('--lb-policy', default='prefix_affinity',
                        help='replica-plane LB policy '
                             '(prefix_affinity | round_robin | '
                             'least_load)')
    parser.add_argument('--ab-policies', action='store_true',
                        help='run the identical fleet workload under '
                             'prefix_affinity AND round_robin and '
                             'emit one combined JSON object (the '
                             'committed BENCH_serve_fleet record)')
    parser.add_argument('--prefix-groups', type=int, default=None,
                        metavar='G',
                        help='number of DISTINCT shared system '
                             'prompts (sessions) under '
                             '--shared-prefix. Fleet mode (default '
                             '8): affinity pins each group to one '
                             'replica while round-robin caches every '
                             'group everywhere. Single-server mode '
                             '(default 1): >1 exercises prefix-cache '
                             'RESIDENCY — the regime int8 KV pages '
                             'double')
    parser.add_argument('--stub-replicas', action='store_true',
                        help='fleet mode with model-free stub '
                             'replicas (replica_plane/stub.py): '
                             'deterministic control-plane smoke, no '
                             'XLA — the tier-1 CI mode')
    parser.add_argument('--stub-cache-pages', type=int, default=64,
                        help='stub replica prefix-cache capacity '
                             '(pages); bound it below the working '
                             'set to make prefix duplication '
                             'measurable')
    parser.add_argument('--stub-token-sleep-ms', type=float,
                        default=1.0,
                        help='stub replica per-token engine-lock '
                             'hold (the decode cadence)')
    parser.add_argument('--stub-prefill-ms-per-token', type=float,
                        default=0.0,
                        help='stub replica simulated prefill cost '
                             'per missed prompt token (held in '
                             'page-sized engine-lock chunks — the '
                             'contention long prompts inflict on '
                             'co-resident decode streams)')
    parser.add_argument('--prefill-replicas', type=int, default=0,
                        metavar='N',
                        help='fleet mode: N additional prefill-role '
                             'replicas (disaggregated serving); '
                             'long prompts route to them and hand '
                             'their KV chains to the decode pool')
    parser.add_argument('--disagg-prompt-threshold', type=int,
                        default=256, metavar='T',
                        help='LB prompt-length threshold (tokens) '
                             'for routing to the prefill pool')
    parser.add_argument('--long-prompt-len', type=int, default=0,
                        metavar='L',
                        help='token length of --long-prompt-frac '
                             'prompts (0 = derived from '
                             '--max-total-len; set explicitly for '
                             'stub fleets, which have no real '
                             'context limit)')
    parser.add_argument('--kv-spill-bytes', type=int, default=0,
                        metavar='B',
                        help='forwarded to serve_lm '
                             '--kv-spill-bytes (tiered prefix '
                             'cache: evicted pages spill to host '
                             'RAM and restore on hit)')
    parser.add_argument('--kv-cold-dir', default=None, metavar='DIR',
                        help='forwarded to serve_lm --kv-cold-dir')
    parser.add_argument('--disagg-ab', action='store_true',
                        help='run the long-prompt-fraction sweep '
                             '{0, 0.25, 0.5} over equal-size '
                             'UNIFIED vs DISAGGREGATED stub fleets '
                             'and emit one combined JSON object '
                             '(the committed BENCH_disagg sweep). '
                             'Implies --stub-replicas')
    parser.add_argument('--storm-ab', action='store_true',
                        help='run the identical workload through a '
                             'control fleet, a zone-storm fleet '
                             'answering preemptions with live '
                             'KV-chain migration, and a --no-migrate '
                             'full-replay fleet, and emit one '
                             'combined JSON object (the committed '
                             'BENCH_migrate record). Implies '
                             '--stub-replicas; needs --fault-plan '
                             '(default: examples/fault_plans/'
                             'decode_zone_storm.json)')
    parser.add_argument('--storm-zone', default='us-east5-b',
                        help='zone the storm plan scopes to; the '
                             'first --storm-spot replicas carry it')
    parser.add_argument('--storm-spot', type=int, default=1,
                        help='how many replicas are spot (zoned) — '
                             'the preemption victims')
    parser.add_argument('--storm-seed', type=int, default=2026,
                        help='FLEET-SHARED stub seed: migrated '
                             'outputs are checked bit-for-bit '
                             'against the closed-form control row')
    parser.add_argument('--spill-ab', action='store_true',
                        help='run the identical pool-pressured '
                             'workload with and without the '
                             'host-RAM spill tier and emit one '
                             'combined JSON object (the committed '
                             'BENCH_disagg spill record). '
                             'Single-server llama-tiny mode; use '
                             'with --kv-pool-bytes + '
                             '--shared-prefix + --prefix-groups')
    parser.add_argument('--state-dir', default=None, metavar='DIR',
                        help='fleet mode: journal replica lifecycle '
                             'to DIR/<policy>/fleet.journal (the '
                             'crash-only controller contract; see '
                             'serve_fleet --state-dir)')
    parser.add_argument('--adapters', type=int, default=0,
                        metavar='N',
                        help='multi-LoRA mode (single-server): '
                             'generate N random adapter artifacts, '
                             'start serve_lm with --adapter-dir, and '
                             'target adapters per request via the '
                             '`model` field (assignment from '
                             '--adapter-mix, deterministic)')
    parser.add_argument('--adapter-mix', default='zipf',
                        choices=['zipf', 'uniform'],
                        help='per-request adapter assignment: zipf '
                             '(weight 1/(k+1) — few hot tenants, '
                             'exercises LRU churn) or uniform')
    parser.add_argument('--adapter-rank', type=int, default=8,
                        help='rank of the generated bench adapters')
    parser.add_argument('--max-adapters', type=int, default=8,
                        help='forwarded to serve_lm --max-adapters '
                             '(clamped up to --adapters)')
    parser.add_argument('--adapter-ab', action='store_true',
                        help='run the adapter-mix workload AND an '
                             'all-base workload against identically '
                             'configured servers (adapters loaded '
                             'but untargeted = the zero-overhead '
                             'fast path) and emit one combined JSON '
                             'object (the committed BENCH_lora '
                             'record)')
    parser.add_argument('--repetitive', action='store_true',
                        help='structured (repeated-trigram) prompts — '
                             'the regime speculation accelerates')
    parser.add_argument('--shared-prefix', type=int, default=0,
                        metavar='N',
                        help='prepend one shared N-token system '
                             'prompt to every request — the regime '
                             'prefix caching accelerates (chatbots, '
                             'few-shot templates)')
    parser.add_argument('--no-prefix-caching', action='store_true')
    parser.add_argument('--kv-dtype', choices=['bf16', 'int8'],
                        default=None,
                        help='forwarded to serve_lm --kv-dtype '
                             '(int8 KV pages; default: server '
                             'default bf16)')
    parser.add_argument('--kv-pool-bytes', type=int, default=0,
                        metavar='B',
                        help='forwarded to serve_lm --kv-pool-bytes: '
                             'size the KV pool by DEVICE BYTES so '
                             'bf16/int8 arms spend the same HBM')
    parser.add_argument('--weight-dtype', choices=['bf16', 'int8'],
                        default=None,
                        help='forwarded to serve_lm --weight-dtype '
                             '(int8 per-channel projection weights)')
    parser.add_argument('--tensor', type=int, default=1,
                        help='forwarded to serve_lm --tensor N '
                             '(tensor-parallel serving); on CPU the '
                             'bench arms the server with '
                             'XLA_FLAGS=--xla_force_host_platform_'
                             'device_count=N. The JSON line gains '
                             'per_chip_req_per_sec')
    parser.add_argument('--stages', type=int, default=1,
                        help='forwarded to serve_lm --stages S '
                             '(pipeline-parallel serving over S '
                             'stages; total chips = S x --tensor). '
                             'Needs --engine continuous; per-chip '
                             'normalization divides by the full '
                             '(stage, tensor) mesh')
    parser.add_argument('--quant-ab', action='store_true',
                        help='run bf16-KV vs int8-KV (same '
                             '--kv-pool-bytes) vs int8-KV+int8-'
                             'weights over the identical workload '
                             'and emit one combined JSON object '
                             '(the committed BENCH_quant record). '
                             'Requires --kv-pool-bytes')
    parser.add_argument('--hbm-peak-gbps', type=float, default=2765.0,
                        metavar='GBPS',
                        help='per-chip HBM peak bandwidth for the '
                             'roofline block (default: TPU v5p '
                             '2765 GB/s; on CPU the fraction is a '
                             'sanity denominator only)')
    parser.add_argument('--tensor-ab', action='store_true',
                        help='run --tensor 1 vs --tensor N over the '
                             'identical workload and emit one '
                             'combined JSON object (per-chip req/s '
                             'scaling)')
    parser.add_argument('--pp-ab', action='store_true',
                        help='run TP-only (tensor=T*S) vs TP x PP '
                             '(tensor=T, stages=S) at EQUAL chip '
                             'count over the identical greedy '
                             'workload and emit one combined JSON '
                             'object (the committed BENCH_tp_pp '
                             'record: per-chip decode tokens/s, '
                             'TTFT, closed-form prefill bubble, '
                             'per-stage pool capacity). Requires '
                             '--stages >= 2')
    parser.add_argument('--hf', default=None,
                        help='serve a local HF checkpoint directory')
    parser.add_argument('--ckpt-dir', default=None)
    parser.add_argument('--slo', default=None, metavar='SPEC',
                        help='score the run against declarative SLO '
                             'targets (dim=target,... over '
                             'p99_ttft_ms / p99_itl_ms / error_rate '
                             '/ shed_rate) and attach a machine-'
                             'checkable `slo` block: per-dimension '
                             'pass/fail + budget_consumed '
                             '(observed/target)')
    parser.add_argument('--cpu', action='store_true',
                        help='pin the server to the CPU backend')
    args = parser.parse_args()
    slo_targets = None
    if args.slo:
        from skypilot_tpu.observability import slo as slo_lib
        try:
            slo_targets = slo_lib.parse_slo(args.slo)
        except ValueError as exc:
            parser.error(str(exc))

    def _emit(record: dict) -> None:
        if slo_targets:
            attach_slo(record, slo_targets)
        print(json.dumps(record))

    if args.decode_chunk > 1 and args.engine != 'continuous':
        parser.error('--decode-chunk is a continuous-engine knob; '
                     'the one-shot engine would silently ignore it '
                     '(and the A/B record would lie)')
    if args.stub_replicas and not args.replicas:
        parser.error('--stub-replicas needs --replicas N')
    if args.adapter_ab and not args.adapters:
        parser.error('--adapter-ab needs --adapters N')
    if args.adapters and args.replicas:
        parser.error('--adapters is a single-server mode (fleet '
                     'replicas share no adapter workload assignment)')
    if args.adapters and args.engine != 'continuous':
        parser.error('--adapters needs --engine continuous (batched '
                     'per-slot LoRA lives in the slot engine)')
    if args.quant_ab and not args.kv_pool_bytes:
        parser.error('--quant-ab needs --kv-pool-bytes B (the A/B '
                     'holds pool BYTES constant; page counts follow '
                     'the storage format)')
    if (args.kv_dtype == 'int8' or args.quant_ab) and \
            args.engine != 'continuous':
        parser.error('--kv-dtype int8 needs --engine continuous '
                     '(int8 pages live in the paged slot engine)')
    if args.quant_ab and (args.replicas or args.adapters):
        parser.error('--quant-ab is a single-server mode')
    if args.tensor_ab and (args.replicas or args.adapters):
        parser.error('--tensor-ab is a single-server mode')
    if args.pp_ab:
        if args.replicas or args.adapters:
            parser.error('--pp-ab is a single-server mode')
        if args.stages < 2:
            parser.error('--pp-ab needs --stages >= 2 (the staged '
                         'arm runs tensor x stages; the TP-only arm '
                         'spends the same chips on tensor alone)')
        if args.engine != 'continuous':
            parser.error('--pp-ab needs --engine continuous '
                         '(pipeline-stage dispatch lives in the '
                         'paged slot engine)')
    if args.stages > 1 and args.engine != 'continuous':
        parser.error('--stages needs --engine continuous (serve_lm '
                     '--stages requires --continuous-batching)')

    if args.disagg_ab:
        if args.spill_ab or args.adapters or args.quant_ab:
            parser.error('--disagg-ab composes only with fleet '
                         'knobs (it runs its own stub fleets)')
        args.stub_replicas = True
        if not args.replicas:
            args.replicas = 2
        if not args.long_prompt_len:
            args.long_prompt_len = 512
        _emit(run_disagg_ab(args))
        return
    if args.storm_ab:
        if args.adapters or args.quant_ab or args.disagg_ab:
            parser.error('--storm-ab composes only with fleet '
                         'knobs (it runs its own stub fleets)')
        args.stub_replicas = True
        if not args.replicas:
            args.replicas = 3
        if args.replicas < 2:
            parser.error('--storm-ab needs --replicas >= 2 (the '
                         'storm victims must have survivors to '
                         'evacuate to)')
        if not args.fault_plan:
            args.fault_plan = os.path.join(
                REPO, 'examples', 'fault_plans',
                'decode_zone_storm.json')
        _emit(run_storm_ab(args))
        return
    if args.spill_ab:
        if args.replicas or args.adapters:
            parser.error('--spill-ab is a single-server mode')
        if args.engine != 'continuous':
            parser.error('--spill-ab needs --engine continuous (the '
                         'spill tier lives in the paged slot '
                         'engine)')
        _emit(run_spill_ab(args))
        return

    if args.quant_ab:
        _emit(run_quant_ab(args))
        return
    if args.tensor_ab:
        _emit(run_tensor_ab(args))
        return
    if args.pp_ab:
        _emit(run_pp_ab(args))
        return

    if args.replicas:
        _emit(run_fleet(args))
        return

    if args.adapters:
        import tempfile
        adapter_dir = tempfile.mkdtemp(prefix='serve_bench_lora_')
        names = _make_adapter_artifacts(args, adapter_dir)
        assignment = _adapter_assignment(args, names)
        if args.adapter_ab:
            _emit({
                'bench': 'serve_lora',
                'engine': args.engine,
                'model': args.model,
                'adapters': args.adapters,
                'adapter_mix': args.adapter_mix,
                'adapter_rank': args.adapter_rank,
                'max_adapters': max(args.max_adapters, args.adapters),
                'requests': args.requests,
                'concurrency': args.concurrency,
                'runs': {
                    # adapters loaded AND targeted (the LoRA lanes)
                    'lora_mix': _run_single(args, adapter_dir,
                                            assignment),
                    # adapters configured, every request hits base:
                    # the zero-overhead fast path...
                    'base_only': _run_single(args, adapter_dir, None),
                    # ...measured against a server with no adapter
                    # registry at all (the pre-LoRA control arm).
                    'no_adapters': _run_single(args),
                },
            })
        else:
            _emit(_run_single(args, adapter_dir, assignment))
        return

    _emit(_run_single(args))



if __name__ == '__main__':
    main()
