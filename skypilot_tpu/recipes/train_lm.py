"""Runnable LM-training recipe: the payload of the example task YAMLs.

Consumes the gang-exec env contract (backends/task_codegen.py):
`jax.distributed.initialize` bootstraps from JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID, so `stpu launch` of this script on
a multi-host TPU slice (or multislice) just works. Checkpoints go
through parallel/checkpoints.py (async orbax, GCS-capable) — the
managed-jobs preemption-recovery contract: on relaunch the script
resumes from the latest step in --ckpt-dir.

Usage (see examples/*.yaml):
  python -m skypilot_tpu.recipes.train_lm --model gpt2-124m \
      --steps 100 --seq 1024 --ckpt-dir gs://bucket/ckpts
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

from skypilot_tpu.robustness import faults
from skypilot_tpu.robustness import train_guard


def _maybe_init_distributed() -> None:
    num = int(os.environ.get('JAX_NUM_PROCESSES', '1'))
    if num <= 1:
        return
    import jax
    jax.distributed.initialize(
        coordinator_address=os.environ['JAX_COORDINATOR_ADDRESS'],
        num_processes=num,
        process_id=int(os.environ['JAX_PROCESS_ID']))


def _build_model(name: str, seq: int, remat: bool):
    import jax.numpy as jnp
    if name == 'gpt2-124m':
        from skypilot_tpu.models.gpt import GPT, GPTConfig
        cfg = GPTConfig.gpt2_124m(remat=remat)
        return GPT(cfg), cfg.vocab_size, None
    if name == 'tiny':
        from skypilot_tpu.models.gpt import GPT, GPTConfig
        cfg = GPTConfig.tiny(remat=remat)
        return GPT(cfg), cfg.vocab_size, None
    if name == 'llama3-8b':
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        cfg = LlamaConfig.llama3_8b(max_seq_len=max(seq, 2048), remat=remat)
        return Llama(cfg), cfg.vocab_size, None
    if name == 'llama3-8b-l8':
        # Llama-3-8B at every published width, cut to 8 of its 32
        # layers (the model-configs guide's depth cut: data, not an
        # architecture) so that one 16 GB chip holds the bf16 weights
        # (~2.8B parameters, ~5.6 GB) and a real page pool.
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        cfg = LlamaConfig.llama3_8b(num_layers=8,
                                    max_seq_len=max(seq, 2048),
                                    remat=remat)
        return Llama(cfg), cfg.vocab_size, None
    if name == 'llama-tiny':
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        cfg = LlamaConfig.tiny(remat=remat)
        if seq > cfg.max_seq_len:
            # Long-context runs on the tiny model (serving benchmarks
            # exercising long-prompt regimes): params are seq-length
            # independent (RoPE is computed from positions), so grow
            # the context and scale the KV page pool to keep the same
            # full-depth slot coverage.
            import dataclasses
            grow = -(-seq // cfg.max_seq_len)
            cfg = dataclasses.replace(
                cfg, max_seq_len=seq,
                kv_total_pages=cfg.kv_total_pages * grow)
        return Llama(cfg), cfg.vocab_size, None
    if name == 'mixtral-8x7b':
        from skypilot_tpu.models.mixtral import (Mixtral, MixtralConfig,
                                                 moe_next_token_loss)
        cfg = MixtralConfig.mixtral_8x7b(remat=remat)
        return Mixtral(cfg), cfg.vocab_size, moe_next_token_loss
    if name == 'mixtral-tiny':
        from skypilot_tpu.models.mixtral import (Mixtral, MixtralConfig,
                                                 moe_next_token_loss)
        cfg = MixtralConfig.tiny(remat=remat)
        return Mixtral(cfg), cfg.vocab_size, moe_next_token_loss
    if name == 'deepseek-v2-lite':
        from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
        cfg = DeepseekConfig.v2_lite(max_seq_len=max(seq, 4096),
                                     remat=remat)
        return Deepseek(cfg), cfg.vocab_size, None
    if name == 'deepseek-tiny':
        from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
        cfg = DeepseekConfig.tiny(remat=remat)
        return Deepseek(cfg), cfg.vocab_size, None
    if name == 'deepseek-v32-l5-ep16':
        # DeepSeek-V3.2 at every published width as ONE chip's share of
        # a 16-way expert-parallel deployment: experts 0-15 of 256, an
        # eighth of the vocabulary, one dense and four expert layers
        # (perfbench/configs/deepseek-v32-l5-ep16.json). Serving only:
        # its attention reads the page pool.
        from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
        cfg = DeepseekConfig.v32_l5_ep16(max_seq_len=max(seq, 4096),
                                         remat=remat)
        return Deepseek(cfg), cfg.vocab_size, None
    if name == 'deepseek-v32-tiny':
        from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
        cfg = DeepseekConfig.v32_tiny(remat=remat)
        if seq > cfg.max_seq_len:
            cfg = dataclasses.replace(cfg, max_seq_len=seq)
        return Deepseek(cfg), cfg.vocab_size, None
    if name == 'nemotron3-super-l11-ep4':
        # NVIDIA-Nemotron-3-Super-120B-A12B at every published width as
        # ONE chip's share of a 4-way expert-parallel deployment: one
        # period of 11 layers (5 Mamba-2, 5 expert, 1 attention),
        # experts 0-127 of 512, a quarter of the vocabulary
        # (perfbench/configs/nemotron3-super-l11-ep4.json). Serving
        # only: its state lives in the engine's slots and pages.
        from skypilot_tpu.models.nemotron_h import (NemotronH,
                                                    NemotronHConfig)
        cfg = NemotronHConfig.super_l11_ep4(max_seq_len=max(seq, 4096),
                                            remat=remat)
        return NemotronH(cfg), cfg.vocab_size, None
    if name == 'nemotron-h-tiny':
        from skypilot_tpu.models.nemotron_h import (NemotronH,
                                                    NemotronHConfig)
        cfg = NemotronHConfig.tiny(remat=remat)
        if seq > cfg.max_seq_len:
            cfg = dataclasses.replace(cfg, max_seq_len=seq)
        return NemotronH(cfg), cfg.vocab_size, None
    if name == 'qwen2-7b':
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        cfg = LlamaConfig(vocab_size=152064, num_layers=28,
                          num_heads=28, num_kv_heads=4,
                          embed_dim=3584, mlp_dim=18944,
                          rope_theta=1e6, norm_eps=1e-6,
                          max_seq_len=max(seq, 2048),
                          qkv_bias=True, remat=remat)
        return Llama(cfg), cfg.vocab_size, None
    if name == 'qwen-tiny':
        from skypilot_tpu.models.llama import Llama, LlamaConfig
        cfg = LlamaConfig.tiny(qkv_bias=True, remat=remat)
        return Llama(cfg), cfg.vocab_size, None
    raise ValueError(f'unknown model {name!r}')


#: The loop phases a --metrics-file record carries, by its field.
_RECORD_PHASES = {'data_s': 'train.data', 'dispatch_s': 'train.dispatch',
                  'sync_s': 'train.sync', 'ckpt_s': 'train.checkpoint'}


def _phase_seconds(clock) -> dict:
    return {field: clock.seconds(name)
            for field, name in _RECORD_PHASES.items()}


def _write_step_dump(path: str, step: int, dump: dict, loss,
                     gnorm) -> None:
    """--dump-step: one .npz with `step`, `tokens`, `loss`,
    `grad_norm` and the parameters before the update as float32
    under `param:<tree path>` keys."""
    import jax
    import numpy as np
    flat, _ = jax.tree_util.tree_flatten_with_path(dump['params'])
    arrays = {f'param:{jax.tree_util.keystr(path)}':
              np.asarray(leaf, np.float32) for path, leaf in flat}
    np.savez(path, step=np.int64(step), tokens=dump['tokens'],
             loss=np.float32(loss), grad_norm=np.float32(gnorm),
             **arrays)
    print(f'dump: step {step} -> {path} ({len(arrays)} parameter '
          f'arrays)', flush=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='gpt2-124m')
    parser.add_argument('--steps', type=int, default=100)
    parser.add_argument('--seq', type=int, default=1024)
    parser.add_argument('--global-batch', type=int, default=0,
                        help='0 = 8 per device')
    parser.add_argument('--data', default='synthetic',
                        help='"synthetic" or a dir/glob of token .bin '
                             'shards')
    parser.add_argument('--ckpt-dir', default=None)
    parser.add_argument('--init-from-hf', default=None, metavar='DIR',
                        help='initialize weights from a local '
                             'HuggingFace checkpoint directory (e.g. '
                             'the target of an hf:// storage COPY) — '
                             'the finetuning path; --model is ignored '
                             'and the architecture comes from the '
                             "checkpoint's config.json "
                             '(models/hf_import.py)')
    parser.add_argument('--ckpt-every', type=int, default=50)
    parser.add_argument('--ckpt-interval', default=None,
                        metavar='auto|SECONDS',
                        help='checkpoint cadence as WALL TIME instead '
                             'of --ckpt-every steps: a number of '
                             'seconds, or "auto" to solve the '
                             'Young/Daly optimum tau* = sqrt(2*delta/'
                             'lambda) from the zone preemption rate '
                             '(--preemption-rate) and the checkpoint '
                             'overhead (--ckpt-overhead) — '
                             'jobs/policy.py. The cadence in steps is '
                             'fixed from the measured mean step time '
                             'of the first logged window and printed')
    parser.add_argument('--preemption-rate', type=float, default=None,
                        metavar='PER_HOUR',
                        help='zone spot preemption rate lambda '
                             '(preemptions/hour) for --ckpt-interval '
                             'auto; default: the '
                             'SKYPILOT_PREEMPTION_RATE_PER_HOUR env '
                             'var (set it in the task env, e.g. from '
                             'the catalog\'s per-zone PreemptionRate '
                             'column)')
    parser.add_argument('--ckpt-overhead', type=float, default=None,
                        metavar='SECONDS',
                        help='checkpoint write overhead delta for '
                             '--ckpt-interval auto (default: '
                             'jobs/policy.DEFAULT_CKPT_OVERHEAD_S, '
                             '60s)')
    parser.add_argument('--guard', action='store_true',
                        help='arm the self-supervising trainer '
                             '(robustness/train_guard.py): preemption'
                             '-notice watcher (GCE metadata + '
                             'SIGTERM) checkpoints NOW and exits '
                             'with the typed code 83 the managed-'
                             'jobs controller maps to recovery; '
                             'on-device NaN/spike guard skips bad '
                             'optimizer steps and rolls back to the '
                             'last checkpoint after --rollback-after '
                             'consecutive ones; a step watchdog '
                             'dumps all thread stacks and aborts '
                             'with code 84 on a hung collective or '
                             'stalled data loader')
    parser.add_argument('--spike-factor', type=float, default=10.0,
                        help='grad-norm spike threshold as a '
                             'multiple of its EMA (guard)')
    parser.add_argument('--guard-warmup', type=int, default=10,
                        help='good steps of EMA warmup before spike '
                             'detection arms (guard)')
    parser.add_argument('--rollback-after', type=int, default=3,
                        help='consecutive bad steps before rolling '
                             'back to the last checkpoint (guard)')
    parser.add_argument('--watchdog-deadline', type=float,
                        default=300.0, metavar='SECONDS',
                        help='per-phase step-watchdog deadline; 0 '
                             'disables the watchdog (guard)')
    parser.add_argument('--watchdog-compile-deadline', type=float,
                        default=1800.0, metavar='SECONDS',
                        help='watchdog deadline for the first step '
                             '(covers XLA compilation)')
    parser.add_argument('--preempt-poll', type=float, default=5.0,
                        metavar='SECONDS',
                        help='preemption-notice metadata poll '
                             'interval (guard)')
    parser.add_argument('--lora', type=int, default=0, metavar='RANK',
                        help='LoRA finetune: freeze the base params '
                             'and train rank-RANK A/B factors on the '
                             'attention (and optionally MLP) '
                             'projections (models/lora.py). The '
                             'trained factors are saved as a serving '
                             'adapter artifact (--adapter-out) that '
                             'serve_lm --adapter-dir loads '
                             'unmodified. Llama-family models only')
    parser.add_argument('--lora-alpha', type=float, default=0.0,
                        help='LoRA alpha (delta scale = alpha/rank); '
                             '0 = alpha = rank (scale 1.0)')
    parser.add_argument('--lora-targets', default='attn',
                        choices=['attn', 'mlp', 'attn-mlp'],
                        help='projections the adapter touches: attn '
                             '(q/k/v/o, the default), mlp '
                             '(gate/up/down), or both')
    parser.add_argument('--adapter-out', default=None, metavar='DIR',
                        help='where --lora writes the adapter '
                             'artifact (adapter_config.json + '
                             'adapter_weights.npz). Default: '
                             '<--ckpt-dir>/adapter, or ./adapter_out '
                             'without a checkpoint dir')
    parser.add_argument('--lr', type=float, default=3e-4)
    parser.add_argument('--tensor', type=int, default=1,
                        help='tensor-parallel mesh axis size')
    parser.add_argument('--expert', type=int, default=1)
    parser.add_argument('--pipeline-stages', type=int, default=1,
                        help='GPipe pipeline parallelism over a stage '
                             'mesh axis (parallel/pipeline.py; '
                             'GPT/Llama/Mixtral/DeepSeek). Composes '
                             'with --tensor/--expert (sharded WITHIN '
                             'each stage) and data parallelism; '
                             'uneven num_layers pads with masked '
                             'identity slots')
    parser.add_argument('--microbatches', type=int, default=0,
                        help='pipeline microbatches (0 = 4 x stages; '
                             'utilization = M / (M + stages - 1))')
    parser.add_argument('--pipeline-schedule', default='gpipe',
                        choices=['gpipe', '1f1b', 'interleaved'],
                        help='pipeline execution schedule (parallel/'
                             'pipeline_schedule.py): gpipe = fused '
                             'fill/drain scan (activation memory '
                             'O(microbatches)); 1f1b = one-forward-'
                             'one-backward, caps live activations at '
                             'O(stages) so microbatches — and with '
                             'them the bubble fraction — can scale; '
                             'interleaved = 1f1b over --virtual-'
                             'stages layer chunks per device, '
                             'dividing the bubble fraction by v')
    parser.add_argument('--virtual-stages', type=int, default=0,
                        help='layer chunks per device for '
                             '--pipeline-schedule interleaved '
                             '(0 = auto: 2 for interleaved, 1 '
                             'otherwise)')
    parser.add_argument('--overlap', action='store_true',
                        help='overlap collectives with compute: adds '
                             "the TPU compiler's async-collective "
                             'latency-hiding flags to LIBTPU_INIT_ARGS '
                             '(read by libtpu only) and, with --zero1, '
                             'buckets the '
                             'grad reduce-scatter per parameter leaf '
                             'so it issues as backward produces each '
                             'leaf instead of one fused update after '
                             'the full backward')
    parser.add_argument('--seq-parallel', type=int, default=1,
                        help='context-parallel mesh axis size '
                             '(ring attention)')
    parser.add_argument('--no-fused-xent', action='store_true',
                        help='disable the fused blockwise LM-head '
                             'cross-entropy (ops/fused_xent.py) and '
                             'materialize the full [B,S,V] logits — '
                             'the escape hatch; fused is the default '
                             'whenever the model supports it')
    parser.add_argument('--zero1', action='store_true',
                        help='ZeRO-1: shard optimizer moments (Adam '
                             'm/v) over the data mesh axis — cuts '
                             'per-chip optimizer HBM by the data-'
                             'parallel degree with step-identical '
                             'math (GSPMD reduce-scatters grads into '
                             'the shards and all-gathers updated '
                             'params)')
    parser.add_argument('--remat', action='store_true')
    parser.add_argument('--log-every', type=int, default=10)
    parser.add_argument('--metrics-file', default=None, metavar='PATH',
                        help='append one JSONL record per --log-every '
                             'window: step, step_time_s, '
                             'tokens_per_sec, loss, grad_norm, and an '
                             'achieved-MFU estimate '
                             '(observability/step_metrics.py) — the '
                             'machine-readable twin of the printed '
                             'log line')
    parser.add_argument('--trace-file', default=None, metavar='PATH',
                        help='write a Chrome-trace timeline (load in '
                             'Perfetto) with per-phase spans — init, '
                             'data, step, checkpoint — same format as '
                             'SKYPILOT_TIMELINE_FILE_PATH, enabled '
                             'from the CLI')
    parser.add_argument('--dump-step', nargs=2, default=None,
                        metavar=('N', 'FILE'),
                        help='write step N as one .npz to FILE: its '
                             'input tokens, the float32 parameters '
                             'before its update, and the loss and '
                             'gradient norm the step then reports '
                             '(N counts from 0: the state\'s step '
                             'before the update) — what a plain '
                             'reference needs to check the step; no '
                             'cost without the flag')
    parser.add_argument('--profile', default=None, metavar='DIR',
                        help='capture a jax.profiler trace '
                             '(TensorBoard/Perfetto-readable) of a few '
                             'steady-state steps into DIR — the MFU '
                             'triage tool: fusion gaps, transfer '
                             'stalls, collective overlap all show up '
                             'in the trace')
    parser.add_argument('--profile-steps', default='4:8',
                        metavar='START:STOP',
                        help='step window to trace (after compile; '
                             'default 4:8)')
    parser.add_argument('--cpu', action='store_true',
                        help='pin the CPU backend (smoke/dev runs)')
    args = parser.parse_args()

    t_start = time.perf_counter()
    if args.overlap:
        # libtpu reads LIBTPU_INIT_ARGS at backend init — extend it
        # before any device access. (Not XLA_FLAGS: the host-side
        # parser aborts on --xla_tpu_* flags it does not know.)
        from skypilot_tpu.parallel.train import OVERLAP_LIBTPU_FLAGS
        existing = os.environ.get('LIBTPU_INIT_ARGS', '')
        add = [f for f in OVERLAP_LIBTPU_FLAGS
               if f.split('=')[0] not in existing]
        if add:
            os.environ['LIBTPU_INIT_ARGS'] = (
                existing + ' ' + ' '.join(add)).strip()
            print(f'overlap: LIBTPU_INIT_ARGS += {" ".join(add)}',
                  flush=True)

    if args.cpu:
        import jax
        jax.config.update('jax_platforms', 'cpu')
    _maybe_init_distributed()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()

    from skypilot_tpu.observability import tracing
    from skypilot_tpu.utils import timeline
    if args.trace_file:
        timeline.enable(args.trace_file)

    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel.train import (ShardedTrainer,
                                             default_optimizer, shard_batch)

    n_dev = len(jax.devices())
    proc_id = jax.process_index()
    if args.microbatches and args.pipeline_stages <= 1:
        raise SystemExit('--microbatches only applies with '
                         '--pipeline-stages > 1')
    if args.overlap and not args.zero1 and args.pipeline_stages <= 1:
        raise SystemExit('--overlap buckets the grad reduce-scatter '
                         'onto the ZeRO-1 moment layout; add --zero1 '
                         '(under --pipeline-stages it only sets the '
                         'compiler latency-hiding flags)')
    if args.virtual_stages and args.pipeline_schedule != 'interleaved':
        raise SystemExit('--virtual-stages only applies with '
                         '--pipeline-schedule interleaved')
    if args.pipeline_schedule != 'gpipe' and args.pipeline_stages <= 1:
        raise SystemExit('--pipeline-schedule needs '
                         '--pipeline-stages > 1')
    if args.lora and args.pipeline_stages > 1:
        raise SystemExit('--lora needs the sharded trainer (the '
                         'GPipe path splits params per stage); '
                         'drop one')
    if args.ckpt_interval is not None:
        if not args.ckpt_dir:
            raise SystemExit('--ckpt-interval needs --ckpt-dir')
        if args.ckpt_interval != 'auto':
            try:
                if float(args.ckpt_interval) <= 0:
                    raise ValueError
            except ValueError:
                raise SystemExit('--ckpt-interval takes "auto" or a '
                                 'positive number of seconds') \
                    from None
        elif args.preemption_rate is None and not os.environ.get(
                'SKYPILOT_PREEMPTION_RATE_PER_HOUR'):
            raise SystemExit(
                '--ckpt-interval auto needs the zone preemption '
                'rate: pass --preemption-rate or set '
                'SKYPILOT_PREEMPTION_RATE_PER_HOUR')
    if args.pipeline_stages > 1:
        # v2: tensor and expert shard WITHIN each pipeline stage
        # (shard_map auto axes — GSPMD inserts the within-stage
        # collectives); sequence parallelism stays exclusive (the
        # ring-attention dispatch assumes the non-pipeline trainer).
        if args.seq_parallel != 1:
            raise SystemExit('--pipeline-stages does not compose with '
                             '--seq-parallel; drop one')
        inner = args.pipeline_stages * args.tensor * args.expert
        if n_dev % inner:
            raise SystemExit(
                f'{n_dev} devices not divisible by stages x tensor x '
                f'expert = {inner}')
        mesh_cfg = mesh_lib.MeshConfig(
            data=n_dev // inner,
            stage=args.pipeline_stages,
            tensor=args.tensor, expert=args.expert)
    else:
        mesh_cfg = mesh_lib.MeshConfig.auto(n_dev, tensor=args.tensor,
                                            expert=args.expert,
                                            seq=args.seq_parallel)
    mesh = mesh_lib.make_mesh(mesh_cfg)
    if proc_id == 0:
        print(f'devices={n_dev} {mesh_lib.mesh_summary(mesh)}', flush=True)

    hf_params = None
    if args.init_from_hf:
        from skypilot_tpu.models import hf_import
        model, hf_params = hf_import.load_hf_checkpoint(
            args.init_from_hf, max_seq_len=max(args.seq, 128),
            remat=args.remat)
        vocab_size = model.config.vocab_size
        from skypilot_tpu.models.mixtral import (Mixtral,
                                                 moe_next_token_loss)
        loss_fn = (moe_next_token_loss if isinstance(model, Mixtral)
                   else None)
        if proc_id == 0:
            print(f'initializing from HF checkpoint {args.init_from_hf} '
                  f'({type(model).__name__}, vocab={vocab_size})',
                  flush=True)
    else:
        model, vocab_size, loss_fn = _build_model(args.model, args.seq,
                                                  args.remat)
    batch = args.global_batch or 8 * n_dev
    lora_spec = None
    if args.lora:
        from skypilot_tpu.models import lora as lora_lib
        lora_spec = lora_lib.LoraSpec(
            rank=args.lora,
            alpha=args.lora_alpha or float(args.lora),
            targets=lora_lib.targets_from_name(args.lora_targets))
    # Both trainers return (loss, grad_norm) when a record or a
    # --dump-step wants the norm — and with --guard, (loss,
    # grad_norm, bad).
    has_gnorm = (args.metrics_file is not None or
                 args.dump_step is not None)
    tx = default_optimizer(learning_rate=args.lr, warmup_steps=10,
                           total_steps=max(args.steps, 20))
    if args.pipeline_stages > 1:
        from skypilot_tpu.models.gpt import GPT
        from skypilot_tpu.models.llama import Llama
        from skypilot_tpu.models.mixtral import Mixtral
        from skypilot_tpu.parallel.pipeline import PipelinedLM
        if not isinstance(model, (GPT, Llama, Mixtral)):
            raise SystemExit('--pipeline-stages supports the GPT, '
                             'Llama, and Mixtral families (v1)')
        microbatches = args.microbatches or 4 * args.pipeline_stages
        denom = microbatches * mesh_cfg.data
        if batch % denom:
            batch = max(denom, (batch // denom) * denom)
            if proc_id == 0:
                print(f'pipeline: rounding global batch to {batch} '
                      f'({microbatches} microbatches x '
                      f'data={mesh_cfg.data})', flush=True)
        if (args.no_fused_xent or args.zero1) and proc_id == 0:
            print('pipeline trainer: --no-fused-xent/--zero1 ignored '
                  '(the pipeline path computes its head per-stage '
                  'and keeps per-stage opt state)', flush=True)
        virtual = args.virtual_stages or (
            2 if args.pipeline_schedule == 'interleaved' else 1)
        try:
            pp = PipelinedLM(model, mesh,
                             num_microbatches=microbatches,
                             schedule=args.pipeline_schedule,
                             virtual_stages=virtual)
        except ValueError as e:
            raise SystemExit(f'--pipeline-schedule: {e}') from None
        if proc_id == 0:
            print(f'pipeline schedule: {pp.schedule.describe()}',
                  flush=True)
        example = jnp.zeros((batch, args.seq), jnp.int32)
        state = pp.init(jax.random.PRNGKey(0), example, tx)
        if hf_params is not None:
            hf_params = pp.split_params(hf_params)
        step_fn = pp.make_train_step(
            tx, guard=args.guard,
            collect_grad_norm=has_gnorm)
        pipeline_bubble_frac = pp.schedule.bubble_fraction
        from skypilot_tpu.observability import catalog
        catalog.gauge('skypilot_train_pipeline_bubble_fraction').set(
            pipeline_bubble_frac)
    else:
        kwargs = {} if loss_fn is None else {'loss_fn': loss_fn}
        trainer = ShardedTrainer(
            model, mesh, tx=tx,
            # None = auto: fused whenever the model supports it (all
            # bundled families do; an hf-imported exotic module
            # without return_hidden falls back to the naive path).
            fused_xent=False if args.no_fused_xent else None,
            zero1=args.zero1,
            overlap=args.overlap,
            # --metrics-file wants grad_norm in every record; --guard
            # needs it unconditionally (the trainer forces it on and
            # computes the norm once for both consumers).
            collect_grad_norm=has_gnorm,
            guard=args.guard,
            lora=lora_spec,
            **kwargs)
        if proc_id == 0:
            print(f'fused_xent={trainer.fused_xent} '
                  f'zero1={args.zero1} overlap={args.overlap} lora='
                  f'{args.lora or "off"}', flush=True)

        example = jnp.zeros((batch, args.seq), jnp.int32)
        with timeline.Event('train/init'):
            state = trainer.init(jax.random.PRNGKey(0), example)
        step_fn = trainer.make_train_step(example)
        pipeline_bubble_frac = None
    if hf_params is not None:
        # Replace the random init with the imported weights, placed
        # with the SAME shardings the trainer chose (device_put
        # against the initialized leaves' shardings — fsdp/tp/stage-
        # safe). Fresh optimizer moments are correct for a finetune
        # start. With --lora only the frozen base half is replaced
        # (the fresh factors ARE the finetune).
        place = lambda init_leaf, w: jax.device_put(  # noqa: E731
            jnp.asarray(w, init_leaf.dtype), init_leaf.sharding)
        if args.lora:
            state = state.replace(params={
                'base': jax.tree.map(place, state.params['base'],
                                     hf_params),
                'lora': state.params['lora']})
        else:
            state = state.replace(params=jax.tree.map(
                place, state.params, hf_params))
        del hf_params

    # Checkpoint resume (preemption recovery path).
    mgr = None
    if args.ckpt_dir:
        from skypilot_tpu.parallel.checkpoints import CheckpointManager
        # Interval mode gates the cadence host-side (it can change
        # once the step cost is measured), so orbax itself must not
        # filter steps.
        mgr = CheckpointManager(
            args.ckpt_dir,
            save_interval_steps=(1 if args.ckpt_interval is not None
                                 else args.ckpt_every))
        latest = mgr.latest_step()
        if latest is not None:
            # restore() verifies sha256 manifests and falls back to
            # the newest verifying step if the latest is corrupt —
            # report the step actually read, not the one asked for.
            state = mgr.restore(state, latest)
            restored = mgr.last_restored_step
            if restored != latest:
                print(f'checkpoint step {latest} corrupt; resumed '
                      f'from step {restored} instead', flush=True)
            else:
                print(f'resumed from checkpoint step {restored}',
                      flush=True)

    # Data.
    loader = None
    if args.data != 'synthetic':
        import glob
        paths = sorted(glob.glob(os.path.join(args.data, '*.bin'))
                       if os.path.isdir(args.data) else glob.glob(args.data))
        from skypilot_tpu.data.token_loader import TokenLoader
        loader = TokenLoader(paths, batch=batch, seq=args.seq,
                             rank=proc_id, world=jax.process_count())

    rng = np.random.default_rng(0)
    start_step = int(state.step)
    # Fire-site context for the train.* fault points: scoped rules
    # can target the first launch ({"resume": "0"}) and leave the
    # checkpoint-resumed run alone.
    resume_ctx = {'resume': '1' if start_step > 0 else '0'}

    def next_tokens():
        # Chaos: a delay rule here is a stalled data loader — the
        # step watchdog must abort past its deadline.
        faults.point('train.data_next', **resume_ctx)
        if loader is not None:
            arr = loader.next_batch()[:, :-1].astype(np.int32)
        else:
            arr = rng.integers(0, vocab_size, (batch, args.seq),
                               dtype=np.int32)
        return shard_batch(jnp.asarray(arr), mesh)

    prof_start = prof_stop = -1
    if args.profile and proc_id == 0:
        prof_start, prof_stop = (int(x) for x in
                                 args.profile_steps.split(':'))
    profiling = False

    # Step telemetry (--metrics-file): one JSONL record per logged
    # window.
    emitter = None
    if args.metrics_file and proc_id == 0:
        from skypilot_tpu.observability.step_metrics import StepMetrics
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(state.params))
        emitter = StepMetrics(args.metrics_file, n_params=n_params,
                              n_devices=n_dev)
        print(f'step metrics -> {args.metrics_file} '
              f'(n_params={n_params:,})', flush=True)

    # Self-supervising guards (--guard): preemption-notice watcher,
    # on-device NaN/spike skip + rollback, step watchdog.
    sup = None
    if args.guard:
        sup = train_guard.TrainSupervisor(
            spike_factor=args.spike_factor,
            warmup_steps=args.guard_warmup,
            rollback_after=args.rollback_after,
            watchdog_deadline_s=args.watchdog_deadline,
            compile_deadline_s=args.watchdog_compile_deadline,
            notice_poll_s=args.preempt_poll,
            ctx=resume_ctx)
        sup.start()
        if proc_id == 0:
            wd = (f'{args.watchdog_deadline:.0f}s'
                  if args.watchdog_deadline > 0 else 'off')
            print(f'train-guard armed: spike_factor='
                  f'{args.spike_factor} warmup={args.guard_warmup} '
                  f'rollback_after={args.rollback_after} '
                  f'watchdog={wd} preempt_poll='
                  f'{args.preempt_poll:.1f}s', flush=True)

    # Checkpoint cadence: steps (--ckpt-every) or wall time
    # (--ckpt-interval SECONDS | auto). Auto solves the Young/Daly
    # optimum from the zone preemption rate; either interval form is
    # converted to steps from the measured mean step time of the
    # first logged window (compile inflates that window, so the
    # first estimate errs toward checkpointing too OFTEN — the safe
    # side).
    ckpt_every_steps = args.ckpt_every
    ckpt_interval_s = None
    if args.ckpt_interval == 'auto':
        from skypilot_tpu.jobs import policy as jobs_policy
        rate = (args.preemption_rate
                if args.preemption_rate is not None else
                float(os.environ['SKYPILOT_PREEMPTION_RATE_PER_HOUR']))
        overhead = (args.ckpt_overhead
                    if args.ckpt_overhead is not None else
                    jobs_policy.DEFAULT_CKPT_OVERHEAD_S)
        ckpt_interval_s = jobs_policy.optimal_checkpoint_interval(
            rate, overhead)
        if proc_id == 0:
            print(f'ckpt-interval auto: lambda={rate}/hr '
                  f'delta={overhead:.0f}s -> tau*='
                  f'{ckpt_interval_s:.0f}s (step cadence fixed after '
                  f'the first logged window)', flush=True)
    elif args.ckpt_interval is not None:
        ckpt_interval_s = float(args.ckpt_interval)
    cadence_fixed = ckpt_interval_s is None

    # The loop's phases (observability/tracing.phase): on the
    # profiler's clock under --profile, Chrome events under
    # --trace-file, and per-step seconds in every --metrics-file
    # record.
    clock = tracing.PhaseClock()
    dump_step, dump_file = ((int(args.dump_step[0]), args.dump_step[1])
                            if args.dump_step else (-1, None))
    dump = None
    t0 = time.perf_counter()
    window_phases = _phase_seconds(clock)
    window_tokens = 0
    window_steps = 0
    step = start_step
    pending = None  # guard: last dispatched step's un-fetched aux
    while step < args.steps:
        if sup is not None and sup.preempted:
            # Preemption notice (metadata, SIGTERM, or injected):
            # checkpoint NOW and exit with the typed code the
            # managed-jobs controller maps to recovery — the resumed
            # run loses at most the step currently in flight.
            if sup.watchdog is not None:
                sup.watchdog.stop()  # a slow save must not trip it
            if proc_id == 0:
                print(f'preemption notice ({sup.preempt_reason}) at '
                      f'step {step}: checkpointing and exiting '
                      f'rc={train_guard.EXIT_PREEMPTED_GRACEFUL}',
                      flush=True)
            if mgr is not None:
                with tracing.phase('train.checkpoint', clock):
                    mgr.save(step, state, force=True)
                    mgr.wait_until_finished()
                    mgr.close()
            if emitter is not None:
                emitter.close()
            if args.trace_file:
                timeline.save()
            sup.stop()
            sys.exit(train_guard.EXIT_PREEMPTED_GRACEFUL)
        with tracing.phase('train.loop', clock):
            # >= not ==: a checkpoint resume may land past prof_start.
            if not profiling and prof_start >= 0 and \
                    prof_start <= step < prof_stop:
                jax.profiler.start_trace(args.profile)
                profiling = True
            first = step == start_step
            if sup is not None:
                sup.beat('data', first_step=first)
            with tracing.phase('train.data', clock):
                tokens = next_tokens()
            if sup is not None:
                sup.beat('step', first_step=first)
            if step == dump_step:
                dump = {'tokens': np.asarray(jax.device_get(tokens)),
                        'params': jax.device_get(state.params)}
            # The step function returns at enqueue: what the device
            # then takes shows in train.sync, not here.
            with tracing.phase('train.dispatch', clock):
                if sup is not None:
                    max_gnorm, loss_scale = sup.step_ctl(step)
                    state, aux = step_fn(state, tokens, max_gnorm,
                                         loss_scale)
                else:
                    faults.point('train.step', step=str(step),
                                 **resume_ctx)
                    state, aux = step_fn(state, tokens)
            if sup is not None:
                loss, gnorm, bad_flag = aux
            elif has_gnorm:
                loss, gnorm = aux
                bad_flag = None
            else:
                loss, gnorm, bad_flag = aux, None, None
            if dump is not None:
                _write_step_dump(dump_file, step, dump, loss, gnorm)
                dump = None
            if first and proc_id == 0:
                # Set-up and steady state are reported apart: the first
                # step carries the compile (or the compile-cache read).
                with tracing.phase('train.sync', clock):
                    jax.block_until_ready(loss)
                print(f'setup: init {t0 - t_start:.1f}s, first step (compile '
                      f'+ run) {time.perf_counter() - t0:.1f}s, compile '
                      f'cache {cache_dir}', flush=True)
            if profiling and step + 1 >= prof_stop:
                # Block so the trace holds COMPLETE device timelines for
                # the window, not just dispatches.
                with tracing.phase('train.sync', clock):
                    jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                profiling = False
                print(f'profile: steps {prof_start}..{prof_stop} traced '
                      f'to {args.profile}', flush=True)
            window_tokens += batch * args.seq
            window_steps += 1
            if sup is not None:
                # Lagged observation: fetch the PREVIOUS step's verdict
                # while this one computes (one-step pipelining keeps the
                # device busy; a rollback discards at most the one step
                # dispatched since).
                if pending is not None:
                    p_step, p_loss, p_gnorm, p_bad = pending
                    pending = None
                    with tracing.phase('train.sync', clock):
                        p_obs = (float(p_loss), float(p_gnorm),
                                 bool(p_bad))
                    verdict = sup.observe(p_step, *p_obs)
                    if verdict == 'rollback':
                        from skypilot_tpu.robustness.errors import (
                            CheckpointNotFoundError)
                        restored = False
                        if mgr is not None:
                            try:
                                state = mgr.restore(state)
                                restored = True
                            except CheckpointNotFoundError:
                                pass
                        if restored:
                            sup.guard.reset_after_rollback()
                            step = int(state.step)
                            t0 = time.perf_counter()
                            window_phases = _phase_seconds(clock)
                            window_tokens = 0
                            window_steps = 0
                            if proc_id == 0:
                                print(f'train-guard: rolled back to '
                                      f'last checkpoint (step {step})',
                                      flush=True)
                            continue
                        # Nothing to roll back to. The params are still
                        # clean (every bad step was skipped on device):
                        # reset the escalation counter and keep skipping.
                        sup.guard.consecutive_bad = 0
                        if proc_id == 0:
                            print('train-guard: rollback requested but '
                                  'no checkpoint available; continuing '
                                  'with per-step skips', flush=True)
                pending = (step, loss, gnorm, bad_flag)
            if mgr is not None and (ckpt_interval_s is None or
                                    (step + 1) % ckpt_every_steps == 0):
                with tracing.phase('train.checkpoint', clock):
                    mgr.save(step + 1, state)
            if profiling and step + 1 >= args.steps:
                # Window ran past the final step: still flush the trace.
                with tracing.phase('train.sync', clock):
                    jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                profiling = False
                print(f'profile: traced through final step {step + 1} '
                      f'to {args.profile}', flush=True)
            boundary = (step + 1) % args.log_every == 0
            if boundary and not cadence_fixed:
                # Every process fixes the cadence (checkpoint saves are
                # collective); proc 0's value is broadcast so clock skew
                # cannot desynchronize the save schedule.
                if sup is not None:
                    sup.beat('commit')
                with tracing.phase('train.sync', clock):
                    jax.block_until_ready(loss)
                mean_step = ((time.perf_counter() - t0) /
                             max(window_steps, 1))
                cadence = max(1, round(ckpt_interval_s /
                                       max(mean_step, 1e-9)))
                if jax.process_count() > 1:
                    from jax.experimental import multihost_utils
                    cadence = int(multihost_utils.broadcast_one_to_all(
                        np.int32(cadence)))
                ckpt_every_steps = cadence
                cadence_fixed = True
                if proc_id == 0:
                    print(f'ckpt cadence: interval '
                          f'{ckpt_interval_s:.0f}s / measured step '
                          f'{mean_step:.3f}s -> checkpoint every '
                          f'{ckpt_every_steps} steps', flush=True)
            if boundary and proc_id == 0:
                if sup is not None:
                    sup.beat('commit')
                # Host-observed drain wait for the in-flight step: the
                # device's critical path (compute + any un-overlapped
                # collectives) still outstanding at the window boundary.
                # On TPU the --profile trace shows WHICH collectives the
                # gap is; this counter tracks whether --overlap shrinks
                # it run-over-run.
                with tracing.phase('train.sync', clock) as drain:
                    jax.block_until_ready(loss)
                collective_wait_s = drain.dur
                from skypilot_tpu.observability import catalog
                catalog.counter(
                    'skypilot_train_collective_wait_seconds_total').inc(
                        collective_wait_s)
                dt = time.perf_counter() - t0
                now_phases = _phase_seconds(clock)
                with tracing.phase('train.log', clock):
                    print(f'step {step + 1}/{args.steps} '
                          f'loss={float(loss):.4f} '
                          f'tokens/s={window_tokens / dt:,.0f}',
                          flush=True)
                    if emitter is not None:
                        n = max(window_steps, 1)
                        emitter.log(
                            step + 1,
                            step_time_s=dt / n,
                            tokens=batch * args.seq,
                            loss=float(loss),
                            grad_norm=(float(gnorm) if gnorm is not None
                                       else None),
                            bubble_frac=pipeline_bubble_frac,
                            collective_wait_s=collective_wait_s,
                            phase_s={k: (now_phases[k] -
                                         window_phases[k]) / n
                                     for k in now_phases})
                t0 = time.perf_counter()
                window_phases = _phase_seconds(clock)
                window_tokens = 0
                window_steps = 0
            step += 1
    if sup is not None:
        if pending is not None:
            p_step, p_loss, p_gnorm, p_bad = pending
            sup.observe(p_step, float(p_loss), float(p_gnorm),
                        bool(p_bad))
        sup.stop()  # before the final save: it can be slow
        if proc_id == 0:
            print(f'train-guard summary: {sup.summary()}', flush=True)
    if mgr is not None:
        with tracing.phase('train.checkpoint', clock):
            mgr.save(args.steps, state, force=True)
            mgr.wait_until_finished()
            mgr.close()
    if lora_spec is not None and proc_id == 0:
        # The produce half of the fine-tune-and-serve loop: the
        # trained factors become a registry-loadable artifact
        # (serve_lm --adapter-dir <parent>, model field = dir name).
        from skypilot_tpu.models import lora as lora_lib
        out_dir = args.adapter_out or (
            os.path.join(args.ckpt_dir, 'adapter') if args.ckpt_dir
            else 'adapter_out')
        lora_np = jax.device_get(state.params['lora'])
        lora_lib.save_adapter(
            out_dir, lora_np, lora_spec,
            base_model=args.init_from_hf or args.model,
            step=int(state.step))
        print(f'adapter artifact -> {out_dir} (rank={lora_spec.rank} '
              f'alpha={lora_spec.alpha} '
              f'targets={list(lora_spec.targets)})', flush=True)
    if emitter is not None:
        emitter.close()
    if proc_id == 0:
        print(f'device memory: {json.dumps(mesh_lib.device_memory())}',
              flush=True)
        print('training done', flush=True)
    if args.trace_file:
        timeline.save()


if __name__ == '__main__':
    main()
