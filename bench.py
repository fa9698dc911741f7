#!/usr/bin/env python
"""GPT-2 124M training throughput on the chip (tokens/s/chip, MFU).

Runs the recipe-model train step (skypilot_tpu/models/gpt.py via the
sharded trainer) on the TPU and prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {"platform": ..., "kind": ..., "count": N}}

Without `--smoke`, a platform other than `tpu` is a non-zero exit:
there is no path on which this carries on on the CPU, and nothing
probes, retries or re-executes. `--smoke` is the CPU rehearsal of the
same code at a tiny size; its line is named for what it is and is
never written under the chip metric's name. A failed sweep, an
out-of-memory step or a `device_kind` with no known peak fails the
run. (The benchmark proper — cells, open-loop load, traces — is
ROADMAP S1's; until then this is the one trainer number, honestly
labelled.)

The reference orchestrator publishes no model-throughput numbers
(BASELINE.md: "published": {}), so vs_baseline is measured against
this repo's own recorded number in BENCH_BASELINE.json when present
and platform-matched (ratio >1 = faster), else 1.0.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--smoke', action='store_true',
                        help='tiny model + CPU-friendly shapes')
    parser.add_argument('--steps', type=int, default=10)
    parser.add_argument('--warmup', type=int, default=2)
    parser.add_argument('--repeats', type=int, default=3,
                        help='timed repeats of the --steps window; the '
                             'JSON line reports the MEDIAN (and stdev) '
                             'so a one-off host stall cannot read as a '
                             'regression — or mask one')
    parser.add_argument('--batch', type=int, default=0,
                        help='global batch size (0 = auto)')
    parser.add_argument('--seq', type=int, default=0)
    parser.add_argument('--inner', type=int, default=0,
                        help='optimizer steps per jitted call via '
                             'lax.scan (0 = auto: 8 off-CPU, 1 on CPU); '
                             'amortizes per-dispatch host overhead')
    parser.add_argument('--sweep-inner', action='store_true',
                        help='measure tokens/s at inner=1/2/4/8 (the '
                             'lax.scan multi-step dispatch-overhead '
                             'amortization) before the headline run; '
                             'results go to stderr, the JSON line is '
                             'unchanged')
    parser.add_argument('--sweep-xent', action='store_true',
                        help='compare the fused blockwise LM-head '
                             'cross-entropy (ops/fused_xent.py) '
                             'against the naive [B,S,V]-materializing '
                             'path on the qwen-tiny config: peak temp '
                             'memory from compiled memory_analysis() '
                             'plus tokens/s for loss+backward, to '
                             'stderr; the JSON line is unchanged')
    parser.add_argument('--no-fused-xent', action='store_true',
                        help='run the headline trainer with the naive '
                             'dense LM-head loss instead of the fused '
                             'blockwise path (A/B escape hatch)')
    parser.add_argument('--sweep-pipeline', action='store_true',
                        help='sweep pipeline schedule x microbatches '
                             '(gpipe/1f1b/interleaved over a stage=4 '
                             'mesh, fixed global batch): step time, '
                             'bubble fraction, peak live activations '
                             'and the activation-memory budget '
                             'verdict per arm; results go to stderr '
                             'and --sweep-pipeline-out, the headline '
                             'JSON line is unchanged')
    parser.add_argument('--sweep-pipeline-out', default=None,
                        metavar='PATH',
                        help='write the --sweep-pipeline arms as one '
                             'JSON artifact (the committed '
                             'BENCH_pipe_* files)')
    parser.add_argument('--profile', default=None, metavar='DIR',
                        help='jax.profiler trace of the FIRST timed '
                             'repeat into DIR (TensorBoard/Perfetto) — '
                             'the MFU triage artifact')
    args = parser.parse_args()

    if args.smoke:
        # The pipeline sweep needs a stage axis: 4 virtual devices.
        count = 4 if args.sweep_pipeline else 1
        os.environ.setdefault(
            'XLA_FLAGS',
            f'--xla_force_host_platform_device_count={count}')

    import jax
    if args.smoke:
        jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp

    from skypilot_tpu.models.gpt import GPT, GPTConfig
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel.train import (ShardedTrainer,
                                             default_optimizer, shard_batch,
                                             shard_batch_stack)
    from skypilot_tpu.observability.step_metrics import \
        peak_flops_per_device
    from skypilot_tpu.utils import compile_cache

    devices = jax.devices()
    n_dev = len(devices)
    platform = devices[0].platform
    device = {'platform': platform, 'kind': devices[0].device_kind,
              'count': n_dev}
    print(f'# device {json.dumps(device)}', file=sys.stderr)
    if not args.smoke and platform != 'tpu':
        print(f'bench.py: FAILED — found platform {platform!r} '
              f'({device["kind"]} x{n_dev}), not a TPU. A chip number '
              f'comes from a chip; `--smoke` is the CPU rehearsal.',
              file=sys.stderr)
        sys.exit(1)
    # Unknown TPU kind -> KeyError here, before any work is timed.
    peak_flops = peak_flops_per_device()
    print(f'# compile cache: {compile_cache.configure()}',
          file=sys.stderr)

    if args.smoke:
        cfg = GPTConfig.tiny()
        batch = args.batch or 8
        seq = args.seq or 128
    else:
        cfg = GPTConfig.gpt2_124m(remat=False)
        batch = args.batch or 8 * n_dev
        seq = args.seq or 1024

    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig.auto(n_dev))
    model = GPT(cfg)
    inner = args.inner or (1 if platform == 'cpu' else 8)

    def build_step(batch_, inner_):
        # fused_xent=None → auto (on): --smoke defaults through the
        # fused blockwise loss, so BENCH rounds track the shipping
        # training hot path; --no-fused-xent pins the naive one.
        trainer = ShardedTrainer(
            model, mesh, tx=default_optimizer(),
            fused_xent=False if args.no_fused_xent else None)
        example = jnp.zeros((batch_, seq), jnp.int32)
        state_ = trainer.init(jax.random.PRNGKey(0), example)
        data = jax.random.randint(jax.random.PRNGKey(1),
                                  (inner_, batch_, seq), 0,
                                  cfg.vocab_size, jnp.int32)
        if inner_ > 1:
            # lax.scan keeps all `inner` optimizer steps in ONE
            # jitted call — one dispatch per timed iteration.
            step_ = trainer.make_multi_step(example, inner_)
            tokens_ = shard_batch_stack(data, mesh)
        else:
            step_ = trainer.make_train_step(example)
            tokens_ = shard_batch(data[0], mesh)
        return state_, step_, tokens_

    def timed_run(state_, step_, tokens_, steps_):
        # The step donates its state buffer: thread the NEW state back
        # or the next call executes on a deleted buffer.
        start_ = time.perf_counter()
        loss_ = None
        for _ in range(steps_):
            state_, loss_ = step_(state_, tokens_)
        jax.block_until_ready(loss_)
        return time.perf_counter() - start_, state_, loss_

    if args.sweep_xent:
        # Fused-vs-naive LM-head loss evidence on the qwen-tiny config
        # (the Qwen2 family is where the [B,S,V] logits hurt most at
        # scale: 152k vocab). Reports XLA's own peak-temp accounting
        # (compiled memory_analysis) and loss+backward throughput.
        import flax.linen as fnn
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        from skypilot_tpu.ops import fused_xent as fx
        from skypilot_tpu.parallel.train import next_token_loss
        qcfg = LlamaConfig.tiny(qkv_bias=True)
        qmodel = Llama(qcfg)
        xb, xs = (4, 128) if args.smoke else (8, 256)
        xtok = jax.random.randint(jax.random.PRNGKey(2), (xb, xs), 0,
                                  qcfg.vocab_size, jnp.int32)
        qparams = fnn.meta.unbox(
            qmodel.init(jax.random.PRNGKey(0), xtok)['params'])
        xhid = qmodel.apply({'params': qparams}, xtok,
                            return_hidden=True)
        xhead = qparams['lm_head']
        xblk = max(64, qcfg.vocab_size // 4)

        def _naive_loss(h, w, t):
            logits = jnp.einsum(
                'bse,ev->bsv', h.astype(qcfg.dtype),
                w.astype(qcfg.dtype),
                preferred_element_type=jnp.float32)
            return next_token_loss(logits, t)

        def _fused_loss(h, w, t):
            return fx.fused_next_token_loss(
                h, w, t, vocab_in_rows=False, block_size=xblk)

        for xname, xfn in (('naive', _naive_loss),
                           (f'fused[block={xblk}]', _fused_loss)):
            xjit = jax.jit(jax.value_and_grad(xfn, argnums=(0, 1)))
            xmem = xjit.lower(xhid, xhead, xtok).compile() \
                .memory_analysis()
            xtemp = getattr(xmem, 'temp_size_in_bytes', None)
            xloss, xg = xjit(xhid, xhead, xtok)
            jax.block_until_ready(xg)
            xt0 = time.perf_counter()
            for _ in range(max(1, args.steps)):
                xloss, xg = xjit(xhid, xhead, xtok)
            jax.block_until_ready(xg)
            xdt = time.perf_counter() - xt0
            xtps = xb * xs * max(1, args.steps) / xdt / n_dev
            print(f'# sweep-xent {xname}: peak_temp_bytes={xtemp} '
                  f'loss={float(xloss):.4f} '
                  f'loss+bwd tokens/s/chip={xtps:,.0f} '
                  f'[{platform}]', file=sys.stderr)

    if args.sweep_inner:
        # Dispatch amortization of the multi-step lax.scan trainer.
        for inner_v in (1, 2, 4, 8):
            s_state, s_step, s_tokens = build_step(batch, inner_v)
            _, s_state, _ = timed_run(s_state, s_step, s_tokens, 1)
            sweep_elapsed, _, _ = timed_run(
                s_state, s_step, s_tokens,
                max(1, args.steps // inner_v))
            tps = (batch * seq * max(1, args.steps // inner_v) * inner_v
                   / sweep_elapsed)
            print(f'# sweep inner={inner_v}: {tps / n_dev:.1f} '
                  f'tokens/s/chip [{platform}]', file=sys.stderr)

    if args.sweep_pipeline:
        # Schedule x microbatch sweep at FIXED global batch: the
        # schedule picker evidence. Bubble fraction and peak live
        # activations come from the schedule object (exact, platform-
        # independent); step time is measured on whatever devices are
        # present; MFU stays null off-TPU. The budget model: a stage
        # can afford S live chunk inputs — exactly what 1F1B
        # guarantees — so GPipe arms with M > S exceed it and their
        # bubble floor is pinned at M = S, while 1f1b/interleaved
        # keep raising M (shrinking the bubble) inside the same
        # memory.
        from skypilot_tpu.parallel.pipeline import PipelinedLM
        from skypilot_tpu.parallel import pipeline_schedule as psched
        pstages = min(4, n_dev)
        psweep_cfg = GPTConfig(
            vocab_size=512, block_size=128, num_layers=8,
            num_heads=4, embed_dim=128, dtype=jnp.float32,
            logits_dtype=jnp.float32)
        pmodel = GPT(psweep_cfg)
        pseq, pbatch = 64, 16
        pmesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(
            stage=pstages, data=n_dev // pstages))
        ptok = jax.random.randint(jax.random.PRNGKey(3),
                                  (pbatch, pseq), 0,
                                  psweep_cfg.vocab_size, jnp.int32)
        arms = []
        for style, vstages in (('gpipe', 1), ('1f1b', 1),
                               ('interleaved', 2)):
            for mcount in (4, 8, 16):
                pp = PipelinedLM(pmodel, pmesh,
                                 num_microbatches=mcount,
                                 schedule=style,
                                 virtual_stages=vstages)
                ptx = default_optimizer()
                pstate = pp.init(jax.random.PRNGKey(0), ptok, ptx)
                pstep = pp.make_train_step(ptx)
                pstate, ploss = pstep(pstate, ptok)  # compile
                jax.block_until_ready(ploss)
                pt0 = time.perf_counter()
                for _ in range(max(2, args.steps // 2)):
                    pstate, ploss = pstep(pstate, ptok)
                jax.block_until_ready(ploss)
                pdt = (time.perf_counter() - pt0) / max(
                    2, args.steps // 2)
                sch = pp.schedule
                mb_tokens = pbatch // (mcount *
                                       pmesh.shape['data']) * pseq
                arm = {
                    'style': style,
                    'virtual_stages': vstages,
                    'microbatches': mcount,
                    'ticks': sch.num_ticks,
                    'bubble_frac': round(sch.bubble_fraction, 4),
                    'peak_live_activations':
                        sch.peak_live_activations,
                    'act_bytes_proxy': sch.activation_bytes(
                        mb_tokens, psweep_cfg.embed_dim),
                    'fits_budget':
                        sch.peak_live_activations <= pstages,
                    'step_time_s': round(pdt, 4),
                    'tokens_per_sec': round(pbatch * pseq / pdt, 1),
                    'loss': round(float(ploss), 4),
                }
                arms.append(arm)
                print(f'# sweep-pipeline {style} v={vstages} '
                      f'M={mcount}: {pdt * 1e3:.0f} ms/step '
                      f'bubble={arm["bubble_frac"]:.1%} '
                      f'peak_live={arm["peak_live_activations"]} '
                      f'fits_budget={arm["fits_budget"]}',
                      file=sys.stderr)
        # The scoreboard claim, machine-checkable: best in-budget
        # bubble per style family vs gpipe's in-budget floor.
        def best_frac(pred):
            fit = [a for a in arms if a['fits_budget'] and pred(a)]
            return min((a['bubble_frac'] for a in fit), default=None)
        summary = {
            'budget_live_activations': pstages,
            'gpipe_bubble_at_budget':
                best_frac(lambda a: a['style'] == 'gpipe'),
            'best_bubble_at_budget':
                best_frac(lambda a: a['style'] != 'gpipe'),
        }
        artifact = {
            'metric': 'pipeline_schedule_sweep',
            'platform': platform,
            'n_dev': n_dev,
            'stages': pstages,
            'seq': pseq,
            'global_batch': pbatch,
            'model': 'gpt-8l-128d',
            'mfu': None if platform != 'tpu' else 'see-arms',
            'closed_form': 'ticks = 2(M*v + S - 1); '
                           'bubble_frac = (S-1)/(M*v + S - 1)',
            'summary': summary,
            'arms': arms,
        }
        if args.sweep_pipeline_out:
            with open(args.sweep_pipeline_out, 'w',
                      encoding='utf-8') as f:
                json.dump(artifact, f, indent=1)
            print(f'# sweep-pipeline artifact -> '
                  f'{args.sweep_pipeline_out}', file=sys.stderr)

    state, step, tokens = build_step(batch, inner)
    # At least one untimed step always runs: it compiles the step and
    # surfaces an out-of-memory batch before the timed section (and
    # --warmup 0 must not leave `loss` unbound). A batch that does not
    # fit fails the run: a smaller one would be another measurement.
    setup0 = time.perf_counter()
    for _ in range(max(1, args.warmup)):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    print(f'# set-up (compile + {max(1, args.warmup)} warm-up steps): '
          f'{time.perf_counter() - setup0:.1f}s', file=sys.stderr)

    # >=1 timed repeats of the same window: median defeats one-off
    # host stalls; stdev quantifies whether a cross-round delta is
    # signal (a 5% regression is only detectable if spread << 5%).
    import statistics
    per_chip_runs = []
    elapsed = None
    for r in range(max(1, args.repeats)):
        if args.profile and r == 0:
            jax.profiler.start_trace(args.profile)
        elapsed, state, loss = timed_run(state, step, tokens,
                                         args.steps)
        if args.profile and r == 0:
            jax.profiler.stop_trace()
            print(f'# profile trace -> {args.profile}',
                  file=sys.stderr)
        run_tps = batch * seq * args.steps * inner / elapsed / n_dev
        per_chip_runs.append(run_tps)
        print(f'# repeat {r + 1}/{args.repeats}: {run_tps:.1f} '
              f'tokens/s/chip ({elapsed:.2f}s)', file=sys.stderr)
    per_chip = statistics.median(per_chip_runs)
    spread = (statistics.stdev(per_chip_runs)
              if len(per_chip_runs) > 1 else 0.0)

    # Training FLOPs/token: 6*N for the weights plus the attention
    # quadratic term 12 * layers * embed * seq (fwd QK^T+AV and their
    # backward, per the PaLM appendix accounting).
    n_params = cfg.num_params()
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.embed_dim * seq
    achieved_tflops_chip = per_chip * flops_per_token / 1e12

    # MFU against the one peak table (observability/step_metrics.py,
    # keyed by device_kind); None off TPU.
    mfu = (achieved_tflops_chip * 1e12 / peak_flops
           if peak_flops else None)

    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             'BENCH_BASELINE.json')
    baseline = None
    recorded = {}
    if not args.smoke and os.path.exists(base_path):
        with open(base_path, 'r', encoding='utf-8') as f:
            recorded = json.load(f)
        # Only compare like with like: a CPU smoke number must not be
        # scored against a recorded TPU baseline.
        if recorded.get('platform') == platform:
            baseline = recorded.get('value')
    vs_baseline = (per_chip / baseline) if baseline else 1.0

    result = {
        # The chip metric's name belongs to chip runs only.
        'metric': ('cpu_smoke_gpt_tiny_train_tokens_per_sec'
                   if args.smoke else
                   'gpt2_124m_train_tokens_per_sec_per_chip'),
        'value': round(per_chip, 1),
        'unit': 'tokens/s' if args.smoke else 'tokens/s/chip',
        'device': device,
        'mfu': round(mfu, 4) if mfu is not None else None,
        'vs_baseline': round(vs_baseline, 3),
        'median': round(per_chip, 1),
        'stdev': round(spread, 1),
        'repeats': len(per_chip_runs),
    }
    # First successful run on each platform becomes the recorded
    # baseline later rounds are scored against (comparisons are
    # platform-matched above; a TPU run REPLACES a CPU-only baseline).
    if baseline is None and not args.smoke:
        recorded_platform = recorded.get('platform')
        if recorded_platform is None or (platform == 'tpu' and
                                         recorded_platform != 'tpu'):
            with open(base_path, 'w', encoding='utf-8') as f:
                json.dump({**result, 'platform': platform,
                           'mfu': round(mfu, 4) if mfu is not None
                           else None,
                           'batch': batch, 'seq': seq,
                           'inner': inner}, f, indent=1)
    last_loss = loss if getattr(loss, 'ndim', 0) == 0 else loss[-1]
    # Extra context on stderr (driver reads the stdout JSON line only).
    print(f'# platform={platform} n_dev={n_dev} batch={batch} seq={seq} '
          f'steps={args.steps}x{inner} elapsed={elapsed:.2f}s '
          f'loss={float(last_loss):.3f} {achieved_tflops_chip:.1f} TFLOP/s/chip'
          + (f' MFU={mfu:.1%}' if mfu is not None else ''),
          file=sys.stderr)
    print(json.dumps(result))


if __name__ == '__main__':
    main()
