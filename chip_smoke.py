#!/usr/bin/env python3
"""Does the system still start on the chip? The trainer and the serving
engine, through the entry points users call, once, or a non-zero exit.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # one four-chip host
    python chip_smoke.py --rehearse   # CPU dress rehearsal, tiny presets

What runs (and nothing else: no sweeps, no A/B, no metrics table):

  device   a child reports platform, device_kind and device count.
           Anything but `tpu` ends the run here; there is no path on
           which it carries on on the CPU.
  train    `python -m skypilot_tpu.recipes.train_lm --model gpt2-124m
           --seq 1024` (all 12 layers, width 768, vocabulary 50304)
           with --ckpt-dir/--ckpt-every/--metrics-file: the loss is
           finite and falls from ~ln(50304), every metrics record has
           an `mfu`.
  resume   the same job again THROUGH THE ORCHESTRATOR, as the README's
           quick start does it (`stpu launch -y --infra local --tpus
           tpu-v5e-N '<the train_lm command>'`): API server, agent and
           job driver stay off JAX, the gang-exec environment reaches a
           real libtpu, orbax restores TPU arrays, training resumes
           from the saved step. Cluster and API server are torn down.
  serve    `python -m skypilot_tpu.recipes.serve_lm
           --continuous-batching` at Llama-3-8B widths (one chip: the
           depth-cut registry entry llama3-8b-l8; four chips:
           llama3-8b at full depth under --tensor 4) answers ragged
           concurrent POST /generate requests, one streamed
           /v1/completions, and one prompt twice (a prefix-cache hit).
           Every answer is a 2xx; the engine's greedy tokens are
           scored by the server's plain forward pass (/v1/completions
           with echo + logprobs) and at every generated position the
           chosen token's log-probability is within LOGPROB_MARGIN of
           that position's best; /stats shows the continuous engine,
           a compiled attention route, a paged pool, a prefix hit,
           true weight bytes, no engine restart and no contained
           error.
  serve-latent  (one chip) the serve stage again on
           `deepseek-v32-tiny`, whose page pool holds MLA's latent
           rows and the indexer's keys (the model's own page layout):
           the same checks, the route `sparse_latent_xla` for the
           decode read and for a prefill chunk (ops/pallas_latent.py's
           kernel refuses the tiny model's widths), no pool-shaped
           copy around either array's write.
  serve-state   (one chip) the serve stage once more on
           `nemotron-h-tiny`, which keeps a Mamba-2 state and a
           convolution's tail BY SLOT beside the K/V pages of its
           attention layer: the same checks without the prefix hit
           (its prefix cache is off), `/stats state_pool`, and no copy
           of a page array or a slot array in the compiled programs.
  (4 chips) one `train_lm --zero1 --overlap` step, per-device bytes
           from both processes (no chip empty, chip 0 at most twice
           the mean) and the sharded-pool guard on the decode function
           as compiled on the chip.

The parent NEVER imports JAX: a chip belongs to one process, so each
stage is a child (`sys.executable -m ...`), one at a time, and a child's
output is forwarded when it fails. Set-up (compile) and steady time are
printed apart per stage with the compile-cache directory in use; they
are by-products of a smoke run, not measurements.

Exit code 0 and, as the LAST line of stdout, one JSON object
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
only when every stage passed on a TPU. Any failure: non-zero, no result
line. `--rehearse` prefixes every line it prints with `[rehearsal]`, the
result line too, so that a CPU run cannot be read as a pass.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import shlex
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
PY = sys.executable

#: How far below the position's best log-probability (under the plain
#: forward pass) the engine's greedy token may score, in nats. Weights
#: and activations are bf16 (eps 2^-8): the engine's logits (paged
#: kernel, chunked prefill, batch of 16) and the scorer's (one dense
#: pass) differ by rounding noise that grows with depth and leaves a
#: near-tie's argmax free to flip. Largest shortfall measured on the
#: chip: 0.057 nats over 8 layers on one chip, 0.170 over 32 layers
#: under --tensor 4 (PERF.md, PR 21). With seeded random weights the
#: best of 128256 logits stands ~5 nats above a typical token, which
#: is where a kernel that drops a page, a mask or the softmax scale
#: lands; 0.5 keeps a 3x distance from the noise and 10x from that.
LOGPROB_MARGIN = 0.5

PREFIX = ''


def say(msg: str = '') -> None:
    for line in str(msg).splitlines() or ['']:
        print(f'{PREFIX}{line}', flush=True)


class SmokeFailure(Exception):
    """A stage failed; the message names the stage and the reason."""


# -- presets ------------------------------------------------------------------
def llama_params(vocab, embed, layers, heads, kv_heads, mlp) -> int:
    """Parameter count of models/llama.py from its published widths
    (untied head, no biases) — computed here because the parent may
    not import the model (it imports JAX)."""
    head_dim = embed // heads
    per_layer = (embed * heads * head_dim            # wq
                 + 2 * embed * kv_heads * head_dim   # wk, wv
                 + heads * head_dim * embed          # wo
                 + 3 * embed * mlp                   # gate, up, down
                 + 2 * embed)                        # two norms
    return 2 * vocab * embed + layers * per_layer + embed


LLAMA3_8B = dict(vocab=128256, embed=4096, heads=32, kv_heads=8,
                 mlp=14336)


#: The latent page layout's stage (one chip): a short request through
#: `deepseek-v32-tiny`, every V3.2 mechanism at a size that compiles
#: in seconds (MLA rows and indexer keys in the page pool, the top-16
#: selection, routed experts by share). 1,475,200 parameters, counted
#: by `jax.eval_shape` (tests/unit_tests/test_deepseek_v32.py).
LATENT = dict(
    serve_model='deepseek-v32-tiny', serve_params=1475200,
    serve_args=['--max-total-len', '256', '--num-slots', '4',
                '--prefill-chunk', '32', '--kv-pool-bytes',
                str(8 << 20)],
    lengths=[(5, 8), (17, 30), (70, 90), (33, 47)],
    max_new=6, attention_impl='sparse_latent_xla',
    # The chunk read's kernel refuses the tiny model's 112 summed
    # values (no whole lane tile): its chunks keep the XLA walk.
    chunk_attention_impl='sparse_latent_xla', default_pages=128,
    prompt_vocab=512)


#: The stage of a model with state BY SLOT beside its pages (one
#: chip): `nemotron-h-tiny`, pattern ME*EM (Mamba-2 state and the
#: convolution's tail a slot, K/V pages of 128-wide heads for the one
#: attention layer, latent experts by share). 648,144 parameters,
#: counted by `jax.eval_shape` (tests/perfbench). No prefix cache: a
#: shared page holds no state to resume from.
STATE = dict(
    serve_model='nemotron-h-tiny', serve_params=648144,
    serve_args=['--max-total-len', '256', '--num-slots', '4',
                '--prefill-chunk', '32', '--kv-pool-bytes',
                str(8 << 20)],
    lengths=[(5, 8), (17, 30), (70, 90), (33, 47)],
    max_new=6, attention_impl='decode', chunk_attention_impl='xla',
    default_pages=128, prompt_vocab=512, prefix_hit=False,
    state_pool={'arrays': {'ssm_state': [8, 16, 16],
                           'conv_state': [576]},
                'layers': 2, 'slots': 4,
                'bytes_per_slot': 2 * (8 * 16 * 16 * 4 + 576 * 2),
                'bytes': 4 * 2 * (8 * 16 * 16 * 4 + 576 * 2)})


def preset(chips: int, rehearse: bool) -> Dict[str, Any]:
    if rehearse:
        return dict(
            train_model='tiny', seq=64, vocab=512, steps=12,
            resume_steps=18, ckpt_every=6, log_every=3,
            serve_model='llama-tiny',
            serve_params=llama_params(512, 128, 2, 4, 2, 384),
            serve_args=(['--tensor', str(chips)] if chips > 1 else []) +
            ['--max-total-len', '256', '--num-slots', '4',
             '--kv-pool-bytes', str(2 << 20)],
            # (lo, hi) prompt-length ranges, one request each; the
            # last is the prompt sent twice.
            lengths=[(5, 8), (17, 30), (40, 60), (33, 47)],
            max_new=6, attention_impl='xla',
            chunk_attention_impl='xla', default_pages=128)
    cfg = dict(
        train_model='gpt2-124m', seq=1024, vocab=50304, steps=30,
        resume_steps=40, ckpt_every=10, log_every=5,
        attention_impl='decode', chunk_attention_impl='xla',
        default_pages=128, max_new=16)
    if chips == 1:
        cfg.update(
            serve_model='llama3-8b-l8',
            serve_params=llama_params(layers=8, **LLAMA3_8B),
            serve_args=['--max-total-len', '2048', '--num-slots', '16',
                        '--kv-pool-bytes', str(3 << 30)],
            # Ranges chosen so the ragged set compiles few prefill
            # shapes: fresh chunks of 32/128/256 tokens, one suffix
            # chunk after a full 256-token chunk, and the short
            # suffix left over by the prefix hit.
            lengths=[(17, 32), (65, 128), (129, 256), (321, 384),
                     (105, 111)])
    else:
        cfg.update(
            serve_model='llama3-8b',
            serve_params=llama_params(layers=32, **LLAMA3_8B),
            # 1.8e9 bytes per chip = 3433 pages: a pool whose element
            # counts are no powers of two, so the pool-collective
            # guard cannot mistake a [4096, 4096] operand for it.
            serve_args=['--tensor', str(chips), '--max-total-len',
                        '2048', '--num-slots', '16',
                        '--kv-pool-bytes', '1800000000'],
            # Full depth compiles slowly and four chips are charged
            # four times: fewer shapes than the one-chip stage.
            lengths=[(17, 32), (129, 256), (105, 111)])
    return cfg


# -- children -----------------------------------------------------------------
class Ctx:
    def __init__(self, args, work: str, env: Dict[str, str],
                 deadline: float) -> None:
        self.args = args
        self.work = work
        self.env = env
        self.deadline = deadline
        self.cfg = preset(args.chips, args.rehearse)
        self.device: Dict[str, Any] = {}

    def remaining(self, want: float) -> float:
        left = self.deadline - time.monotonic()
        if left <= 5:
            raise SmokeFailure('out of time (--deadline reached)')
        return min(want, left)


def tail(path: str, lines: int = 120) -> str:
    try:
        with open(path, 'r', encoding='utf-8', errors='replace') as f:
            return ''.join(f.readlines()[-lines:])
    except OSError as e:
        return f'<no log: {e}>'


def run_child(ctx: Ctx, stage: str, cmd: List[str], timeout: float,
              env: Optional[Dict[str, str]] = None) -> Tuple[str, float]:
    """Run one child to its end, output into `<work>/<stage>.log`.
    Returns (output, seconds); a non-zero exit or a timeout raises with
    the child's own last lines — never `stdout=DEVNULL`."""
    log_path = os.path.join(ctx.work, f'{stage}.log')
    t0 = time.monotonic()
    with open(log_path, 'ab') as log:
        proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                env=env or ctx.env, cwd=REPO,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=ctx.remaining(timeout))
        except subprocess.TimeoutExpired:
            kill_group(proc)
            raise SmokeFailure(
                f'{stage}: no exit after {timeout:.0f}s: '
                f'{" ".join(cmd)}\n--- last output ---\n'
                f'{tail(log_path)}') from None
    dt = time.monotonic() - t0
    with open(log_path, 'r', encoding='utf-8', errors='replace') as f:
        out = f.read()
    if rc != 0:
        raise SmokeFailure(f'{stage}: exit code {rc}: {" ".join(cmd)}\n'
                           f'--- last output ---\n{tail(log_path)}')
    return out, dt


def kill_group(proc: subprocess.Popen, grace: float = 20.0) -> None:
    """SIGTERM the child's process group, SIGKILL what is left."""
    if proc.poll() is not None:
        return
    for sig, wait in ((signal.SIGTERM, grace), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            return
        try:
            proc.wait(timeout=wait)
            return
        except subprocess.TimeoutExpired:
            continue


def kill_strays(marker: str) -> List[int]:
    """Every process this run started carries CHIP_SMOKE_RUN=<marker>
    in its environment (daemons that left our process groups too: the
    API server, the agent). Whatever still lives is killed."""
    me = os.getpid()
    needle = f'CHIP_SMOKE_RUN={marker}'.encode()
    killed = []
    for pid in (int(p) for p in os.listdir('/proc') if p.isdigit()):
        if pid == me:
            continue
        try:
            with open(f'/proc/{pid}/environ', 'rb') as f:
                if needle not in f.read().split(b'\0'):
                    continue
            os.kill(pid, signal.SIGKILL)
            killed.append(pid)
        except (OSError, PermissionError):
            continue
    return killed


# -- stage: device ------------------------------------------------------------
def stage_device(ctx: Ctx) -> None:
    code = ('import json, jax; d = jax.devices(); '
            'print("DEVICE " + json.dumps({"platform": d[0].platform, '
            '"kind": d[0].device_kind, "count": len(d)}))')
    out, dt = run_child(ctx, 'device', [PY, '-c', code], 300)
    lines = [l for l in out.splitlines() if l.startswith('DEVICE ')]
    if not lines:
        raise SmokeFailure(f'device: the child printed no device:\n{out}')
    ctx.device = json.loads(lines[-1][len('DEVICE '):])
    d = ctx.device
    say(f'device: platform={d["platform"]} device_kind={d["kind"]!r} '
        f'count={d["count"]} (backend up in {dt:.1f}s)')
    want = 'cpu' if ctx.args.rehearse else 'tpu'
    if d['platform'] != want:
        raise SmokeFailure(
            f'device: found platform {d["platform"]!r} '
            f'({d["kind"]} x{d["count"]}), not {want!r}. This script '
            f'proves the program on the chip; it does not carry on '
            f'without one (--rehearse is the CPU dress rehearsal).')
    if d['count'] != ctx.args.chips:
        raise SmokeFailure(
            f'device: --chips {ctx.args.chips} but JAX sees '
            f'{d["count"]} device(s)')


# -- stage: train -------------------------------------------------------------
def train_cmd(ctx: Ctx, steps: int) -> List[str]:
    c = ctx.cfg
    return [PY, '-m', 'skypilot_tpu.recipes.train_lm',
            '--model', c['train_model'], '--seq', str(c['seq']),
            '--steps', str(steps), '--log-every', str(c['log_every']),
            '--ckpt-dir', os.path.join(ctx.work, 'ckpt'),
            '--ckpt-every', str(c['ckpt_every']),
            '--metrics-file', os.path.join(ctx.work, 'metrics.jsonl')]


def parse_train(out: str) -> Dict[str, Any]:
    losses, setup, memory, mesh = [], None, None, None
    for line in out.splitlines():
        line = line.strip()
        if line.startswith('step ') and ' loss=' in line:
            step = int(line.split()[1].split('/')[0])
            loss = float(line.split('loss=')[1].split()[0])
            losses.append((step, loss))
        elif line.startswith('setup: '):
            setup = line
        elif line.startswith('devices='):
            mesh = line
        elif line.startswith('device memory: '):
            memory = json.loads(line[len('device memory: '):])
    return {'losses': losses, 'setup': setup, 'memory': memory,
            'mesh': mesh}


def check_losses(stage: str, losses: List[Tuple[int, float]]) -> None:
    if not losses:
        raise SmokeFailure(f'{stage}: the trainer logged no loss')
    bad = [(s, l) for s, l in losses if not math.isfinite(l)]
    if bad:
        raise SmokeFailure(f'{stage}: non-finite loss at {bad}')


def check_memory(stage: str, memory: Optional[List[Dict[str, Any]]],
                 chips: int) -> None:
    """No chip of the mesh empty, chip 0 at most twice the mean — for
    bytes in use and for the peak (a tree initialised whole on chip 0
    and sharded afterwards shows only in the peak)."""
    if not memory:
        raise SmokeFailure(f'{stage}: no per-device memory reported')
    say(f'{stage}: per-device bytes ' + ', '.join(
        f'd{m["id"]}: in_use={m["bytes_in_use"]} '
        f'peak={m["peak_bytes_in_use"]}' for m in memory))
    if memory[0]['bytes_in_use'] is None:
        return      # a backend without memory_stats (the CPU rehearsal)
    if len(memory) != chips:
        raise SmokeFailure(f'{stage}: {len(memory)} devices reported, '
                           f'expected {chips}')
    for key in ('bytes_in_use', 'peak_bytes_in_use'):
        vals = [m[key] for m in memory]
        mean = sum(vals) / len(vals)
        if min(vals) <= 0:
            raise SmokeFailure(f'{stage}: a chip holds nothing '
                               f'({key}={vals})')
        if vals[0] > 2 * mean:
            raise SmokeFailure(
                f'{stage}: chip 0 holds more than twice the mean '
                f'({key}={vals}): something landed on the first chip')


def read_metrics(ctx: Ctx) -> List[Dict[str, Any]]:
    with open(os.path.join(ctx.work, 'metrics.jsonl'), 'r',
              encoding='utf-8') as f:
        return [json.loads(l) for l in f if l.strip()]


def stage_train(ctx: Ctx) -> None:
    c = ctx.cfg
    out, dt = run_child(ctx, 'train', train_cmd(ctx, c['steps']), 600)
    res = parse_train(out)
    losses = res['losses']
    check_losses('train', losses)
    ln_v = math.log(c['vocab'])
    first, last = losses[0][1], losses[-1][1]
    say(f'train: {res["mesh"]}')
    say(f'train: {c["train_model"]} seq {c["seq"]}, {c["steps"]} steps '
        f'in {dt:.1f}s; losses ' +
        ' '.join(f'{s}:{l:.4f}' for s, l in losses) +
        f' (ln(vocab)={ln_v:.4f})')
    if abs(first - ln_v) > 1.0:
        raise SmokeFailure(f'train: first loss {first:.4f} is not near '
                           f'ln({c["vocab"]})={ln_v:.4f}')
    if not last < first:
        raise SmokeFailure(f'train: loss did not fall ({first:.4f} -> '
                           f'{last:.4f})')
    if 'training done' not in out:
        raise SmokeFailure('train: no "training done"')
    records = read_metrics(ctx)
    if not records:
        raise SmokeFailure('train: --metrics-file is empty')
    if not ctx.args.rehearse and any(r.get('mfu') is None
                                     for r in records):
        raise SmokeFailure(f'train: a metrics record has no mfu on the '
                           f'chip: {records[-1]}')
    steady = records[1:] or records
    say(f'train: {res["setup"]}')
    say(f'train: steady step time '
        f'{sum(r["step_time_s"] for r in steady) / len(steady):.4f}s '
        f'over {len(steady)} logged windows after the first; last '
        f'record {json.dumps(records[-1])}')
    if ctx.args.chips > 1:
        check_memory('train', res['memory'], ctx.args.chips)


def stage_overlap(ctx: Ctx) -> None:
    """Four chips: the compiler's latency-hiding flags reach libtpu
    (LIBTPU_INIT_ARGS) and a ZeRO-1 overlap step compiles and runs."""
    c = ctx.cfg
    cmd = [PY, '-m', 'skypilot_tpu.recipes.train_lm',
           '--model', c['train_model'], '--seq', str(c['seq']),
           '--steps', '2', '--log-every', '1', '--zero1', '--overlap']
    out, dt = run_child(ctx, 'overlap', cmd, 600)
    res = parse_train(out)
    check_losses('overlap', res['losses'])
    if 'LIBTPU_INIT_ARGS +=' not in out:
        raise SmokeFailure('overlap: the flags were not set')
    say(f'overlap: train_lm --zero1 --overlap, 2 steps in {dt:.1f}s, '
        f'losses {res["losses"]}; {res["setup"]}')


# -- stage: resume through the orchestrator -----------------------------------
def stage_resume(ctx: Ctx) -> None:
    c = ctx.cfg
    cli = [PY, '-m', 'skypilot_tpu.client.cli']
    cluster = 'chip-smoke'
    job = shlex.join(train_cmd(ctx, c['resume_steps']))
    env = dict(ctx.env,
               SKYPILOT_TPU_HOME=os.path.join(ctx.work, 'sky_home'))
    try:
        out, dt = run_child(
            ctx, 'resume',
            cli + ['launch', '-y', '-c', cluster, '--infra', 'local',
                   '--tpus', f'tpu-v5e-{ctx.args.chips}', job],
            700, env=env)
        res = parse_train(out)
        marker = f'resumed from checkpoint step {c["steps"]}'
        if marker not in out:
            raise SmokeFailure(f'resume: no "{marker}" in the job '
                               f'log:\n{out[-4000:]}')
        if 'finished: SUCCEEDED' not in out:
            raise SmokeFailure(f'resume: the job did not succeed:\n'
                               f'{out[-4000:]}')
        check_losses('resume', res['losses'])
        if not res['losses'] or res['losses'][-1][0] != c['resume_steps']:
            raise SmokeFailure(f'resume: the job did not reach step '
                               f'{c["resume_steps"]}: {res["losses"]}')
        queue, _ = run_child(ctx, 'resume_queue',
                             cli + ['queue', cluster], 120, env=env)
        if 'SUCCEEDED' not in queue:
            raise SmokeFailure(f'resume: `stpu queue` does not show '
                               f'the job SUCCEEDED:\n{queue}')
        say(f'resume: stpu launch --infra local --tpus tpu-v5e-'
            f'{ctx.args.chips} -> {marker}, then steps ' +
            ' '.join(f'{s}:{l:.4f}' for s, l in res['losses']) +
            f' in {dt:.1f}s (provision + job); job SUCCEEDED')
        say(f'resume: {res["setup"]}')
    finally:
        # Tear down whatever came up, also after a failure.
        for what, cmd in (('down', cli + ['down', '-y', cluster]),
                          ('api stop', cli + ['api', 'stop'])):
            try:
                run_child(ctx, 'teardown', cmd, 120, env=env)
            except SmokeFailure as e:
                say(f'resume: teardown `{what}` failed: {str(e)[:500]}')
    say('resume: cluster down, API server stopped')


# -- stage: serve -------------------------------------------------------------
def free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


class Client:
    """HTTP to the server under test. Any status outside 2xx fails the
    run (the server answers a contained engine error with a 4xx/5xx
    and stays up, so the status is the signal)."""

    def __init__(self, base: str) -> None:
        self.base = base

    def _open(self, path: str, body: Optional[dict], timeout: float):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={'Content-Type': 'application/json'})
        try:
            return urllib.request.urlopen(req, timeout=timeout)
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f'serve: {path} answered {e.code}: '
                f'{e.read()[:2000].decode(errors="replace")}') from None

    def json(self, path: str, body: Optional[dict] = None,
             timeout: float = 600.0) -> Any:
        with self._open(path, body, timeout) as resp:
            return json.loads(resp.read())

    def sse(self, path: str, body: dict,
            timeout: float = 600.0) -> List[Any]:
        events = []
        with self._open(path, body, timeout) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith('data: '):
                    payload = line[len('data: '):]
                    events.append(payload if payload == '[DONE]'
                                  else json.loads(payload))
        return events


def make_prompts(ctx: Ctx, seed: int) -> List[List[int]]:
    rng = random.Random(seed)
    vocab = ctx.cfg.get('prompt_vocab') or (
        LLAMA3_8B['vocab'] if not ctx.args.rehearse else 512)
    return [[rng.randrange(1, vocab) for _ in range(rng.randint(lo, hi))]
            for lo, hi in ctx.cfg['lengths']]


def generate_wave(client: Client, prompts: List[List[int]],
                  max_new: int) -> Tuple[List[List[int]], float]:
    """POST /generate for every prompt CONCURRENTLY; rows back in
    prompt order."""
    rows: List[Any] = [None] * len(prompts)
    errors: List[BaseException] = []

    def one(i: int) -> None:
        try:
            out = client.json('/generate', {
                'tokens': [prompts[i]], 'max_new_tokens': max_new,
                'temperature': 0.0})
            rows[i] = out['tokens'][0]
        except BaseException as e:  # re-raised on the main thread
            errors.append(e)

    t0 = time.monotonic()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    dt = time.monotonic() - t0
    for p, row in zip(prompts, rows):
        if row[:len(p)] != p or len(row) != len(p) + max_new:
            raise SmokeFailure(
                f'serve: /generate returned {len(row)} tokens for a '
                f'{len(p)}-token prompt + {max_new} new (or changed '
                f'the prompt)')
    return rows, dt


def score_row(client: Client, row: List[int], prompt_len: int
              ) -> float:
    """Largest shortfall, over the generated positions, of the engine's
    token against the plain forward pass's best token (nats)."""
    out = client.json('/v1/completions', {
        'prompt': row, 'max_tokens': 0, 'echo': True, 'logprobs': 1,
        'temperature': 0.0})
    lp = out['choices'][0]['logprobs']
    if len(lp['token_logprobs']) != len(row):
        raise SmokeFailure('serve: scoring returned '
                           f'{len(lp["token_logprobs"])} positions for '
                           f'{len(row)} tokens')
    worst = 0.0
    for i in range(prompt_len, len(row)):
        chosen = lp['token_logprobs'][i]
        best = max(lp['top_logprobs'][i].values())
        if not (math.isfinite(chosen) and math.isfinite(best)):
            raise SmokeFailure(f'serve: non-finite log-probability at '
                               f'position {i}')
        worst = max(worst, best - chosen)
    return worst


def stage_serve(ctx: Ctx) -> None:
    c = ctx.cfg
    port = free_port()
    cmd = [PY, '-m', 'skypilot_tpu.recipes.serve_lm',
           '--model', c['serve_model'], '--continuous-batching',
           *c['serve_args'], '--port', str(port), '--drain-grace', '5']
    log_path = os.path.join(ctx.work, 'serve.log')
    say(f'serve: {" ".join(cmd[1:])}')
    t0 = time.monotonic()
    with open(log_path, 'ab') as log:
        proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                env=ctx.env, cwd=REPO,
                                start_new_session=True)
    try:
        client = Client(f'http://127.0.0.1:{port}')
        ready = None
        limit = time.monotonic() + ctx.remaining(600)
        while ready is None:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f'serve: serve_lm exited with code '
                    f'{proc.returncode} before it was ready\n'
                    f'--- last output ---\n{tail(log_path)}')
            if time.monotonic() > limit:
                raise SmokeFailure(f'serve: not ready in time\n'
                                   f'--- last output ---\n'
                                   f'{tail(log_path)}')
            try:
                ready = json.loads(urllib.request.urlopen(
                    f'http://127.0.0.1:{port}/', timeout=5).read())
            except (OSError, ValueError):
                time.sleep(1.0)
        ready_s = time.monotonic() - t0
        say(f'serve: ready in {ready_s:.1f}s (weights + pool on '
            f'device): {json.dumps(ready)}')
        for line in tail(log_path, 400).splitlines():
            if line.startswith(('tensor-parallel serving', 'kv cache:',
                                'engine 0: KV cache')):
                say(f'serve: {line}')
        try:
            serve_checks(ctx, client, ready_s)
        except SmokeFailure as e:
            raise SmokeFailure(f'{e}\n--- server log, last lines ---\n'
                               f'{tail(log_path, 60)}') from None
    finally:
        kill_group(proc)
    say(f'serve: server stopped (exit code {proc.returncode})')


def serve_checks(ctx: Ctx, client: Client, ready_s: float) -> None:
    c, seed = ctx.cfg, ctx.args.seed
    max_new = c['max_new']
    prompts = make_prompts(ctx, seed)
    # Wave 1: ragged prompts, concurrently. Everything compiles here.
    rows, first_s = generate_wave(client, prompts, max_new)
    say(f'serve: {len(prompts)} concurrent /generate, prompt lengths '
        f'{[len(p) for p in prompts]}, {max_new} new tokens each: '
        f'{first_s:.1f}s (compiles included)')
    say(f'serve: a served completion (prompt of {len(prompts[0])} '
        f'tokens): {rows[0][len(prompts[0]):]}')
    # The same prompt again: its full pages are now in the prefix cache.
    again, hit_s = generate_wave(client, [prompts[-1]], max_new)
    rows.append(again[0])
    prompts.append(prompts[-1])
    # One streamed OpenAI completion (token-id prompt: no tokenizer).
    t0 = time.monotonic()
    events = client.sse('/v1/completions', {
        'prompt': prompts[0], 'max_tokens': max_new, 'stream': True,
        'temperature': 0.0})
    text = ''.join(e['choices'][0].get('text') or ''
                   for e in events if e != '[DONE]')
    streamed = [int(t) for t in text.split()]
    finishes = [e['choices'][0]['finish_reason'] for e in events
                if e != '[DONE]' and e['choices'][0]['finish_reason']]
    if events[-1:] != ['[DONE]'] or len(streamed) != max_new or \
            finishes != ['length']:
        raise SmokeFailure(
            f'serve: the stream carried {len(streamed)} tokens in '
            f'{len(events)} events, finish {finishes}, last event '
            f'{events[-1:]}; expected {max_new} tokens, "length", '
            f'[DONE]')
    rows.append(prompts[0] + streamed)
    prompts.append(prompts[0])
    say(f'serve: streamed /v1/completions: {len(streamed)} tokens in '
        f'{len(events)} SSE events, {time.monotonic() - t0:.1f}s')
    # The plain forward pass scores every greedy row.
    t0 = time.monotonic()
    gaps = [score_row(client, row, len(p))
            for p, row in zip(prompts, rows)]
    say(f'serve: plain-forward scoring of {len(rows)} rows x {max_new} '
        f'generated positions: chosen-vs-best log-prob shortfall per '
        f'row {[round(g, 4) for g in gaps]} nats (margin '
        f'{LOGPROB_MARGIN}), {time.monotonic() - t0:.1f}s')
    if max(gaps) > LOGPROB_MARGIN:
        raise SmokeFailure(
            f'serve: the engine chose a token {max(gaps):.4f} nats '
            f'below the plain forward pass\'s best (margin '
            f'{LOGPROB_MARGIN}): the engine path computes something '
            f'else than the model')
    # Wave 2: fresh prompts of the same lengths; nothing compiles.
    _, steady_s = generate_wave(client, make_prompts(ctx, seed + 1),
                                max_new)
    say(f'serve: set-up {ready_s + first_s:.1f}s (ready {ready_s:.1f}s '
        f'+ first wave {first_s:.1f}s); steady: the same wave with '
        f'fresh prompts {steady_s:.2f}s, prefix-hit request '
        f'{hit_s:.2f}s')
    stats = client.json('/stats')
    check_stats(ctx, stats)
    guard = client.json('/debug/pool_collectives', timeout=1800)
    say(f'serve: pool_collective_lines and pool_copy_lines on the '
        f'compiled decode and prefill-chunk programs: '
        f'{json.dumps(guard)}')
    copies = guard['copies']
    if ctx.args.rehearse and c.get('state_pool') and copies:
        # XLA:CPU copies the arrays that the live rows' loop of a
        # decode round carries (ops/ssm.ssm_update), every step; the
        # chip's compiler updates them where they lie, which is what
        # this check is for and what the rehearsal cannot show.
        copies = {k: v for k, v in copies.items() if k != 'decode'}
    if not copies or any(copies.values()):
        raise SmokeFailure(
            f'serve: a compiled program copies a whole pool-shaped '
            f'array around its KV write (expected none in '
            f'{sorted(copies or [])}): {guard}')
    if ctx.args.chips > 1:
        check_memory('serve', stats.get('device_memory'),
                     ctx.args.chips)
        if guard['lines'] != [] or guard['mesh_devices'] != \
                ctx.args.chips:
            raise SmokeFailure(
                f'serve: the compiled decode step moves the sharded '
                f'KV pool between chips: {guard}')


def check_stats(ctx: Ctx, stats: Dict[str, Any]) -> None:
    c = ctx.cfg
    keep = {k: stats.get(k) for k in (
        'engine', 'attention_impl', 'chunk_attention_impl', 'kv_cache',
        'engine_restarts',
        'soft_errors', 'healthy', 'decode_calls', 'tokens_committed',
        'preemptions', 'prefill_chunks_run', 'first_tokens_deferred',
        'first_tokens_synced')}
    keep['storage'] = stats.get('storage')
    keep['page_pool'] = stats.get('page_pool')
    keep['prefix_cache'] = stats.get('prefix_cache')
    keep['state_pool'] = stats.get('state_pool')
    keep['requests'] = (stats.get('serving') or {}).get('requests')
    say(f'serve: /stats excerpt {json.dumps(keep)}')
    storage = stats.get('storage') or {}
    pool = stats.get('page_pool') or {}
    problems = []

    def expect(name, got, want):
        if got != want:
            problems.append(f'{name}={got!r} (expected {want!r})')

    expect('engine', stats.get('engine'), 'continuous')
    # The route the code chose for a bf16 pool of 128-wide heads on
    # this backend: the in-repo decode read on TPU — never the XLA
    # reference, the dense cache or interpret mode on the chip.
    expect('attention_impl', stats.get('attention_impl'),
           c['attention_impl'])
    # And for a whole prefill chunk's attention (a bf16 K/V pool's
    # chunks take the gather; a latent pool's the kernel or the walk).
    expect('chunk_attention_impl', stats.get('chunk_attention_impl'),
           c['chunk_attention_impl'])
    expect('engine_restarts', stats.get('engine_restarts'), 0)
    expect('soft_errors', stats.get('soft_errors'), 0)
    expect('healthy', stats.get('healthy'), True)
    # serve_lm's defaults run the plain pipelined loop: no finished
    # prompt's first token goes through the scheduler's blocking fetch.
    expect('first_tokens_synced', stats.get('first_tokens_synced'), 0)
    if not stats.get('first_tokens_deferred', 0) > 0:
        problems.append(f'first_tokens_deferred='
                        f'{stats.get("first_tokens_deferred")!r} '
                        f'(expected every finished prompt)')
    expect('storage.kv_dtype', storage.get('kv_dtype'), 'bf16')
    expect('storage.weight_dtype', storage.get('weight_dtype'), 'bf16')
    expect('storage.weight_bytes', storage.get('weight_bytes'),
           2 * c['serve_params'])
    expect('storage.mesh_devices', storage.get('mesh_devices'),
           ctx.args.chips)
    if not str(stats.get('kv_cache', '')).startswith('paged'):
        problems.append(f'kv_cache={stats.get("kv_cache")!r} '
                        f'(expected the paged pool)')
    if not pool.get('total', 0) > c['default_pages']:
        problems.append(f'page_pool={pool} (expected a pool sized by '
                        f'--kv-pool-bytes, above the default '
                        f'{c["default_pages"]} pages)')
    if c.get('prefix_hit', True):
        if not (stats.get('prefix_cache') or {}).get('hits', 0) > 0:
            problems.append(f'prefix_cache={stats.get("prefix_cache")} '
                            f'(expected a hit)')
    else:
        expect('prefix_cache', stats.get('prefix_cache'), None)
    # A model with state by slot says what a slot keeps beside its
    # pages; every other model says nothing.
    expect('state_pool', stats.get('state_pool'), c.get('state_pool'))
    if ctx.device['platform'] == 'tpu' and storage.get(
            'attention_kernel_unavailable_reason') is not None:
        problems.append('attention_kernel_unavailable_reason='
                        f'{storage["attention_kernel_unavailable_reason"]!r}')
    if problems:
        raise SmokeFailure('serve: /stats: ' + '; '.join(problems))


def stage_serve_latent(ctx: Ctx) -> None:
    """The serve stage again, on the model whose page pool holds
    latent rows: the same checks (the scored completion, the prefix
    hit, the in-place write of BOTH of its pool arrays, no blocking
    first-token fetch), the route `sparse_latent_xla` for both the
    decode read and a chunk's attention."""
    saved = ctx.cfg
    ctx.cfg = dict(saved, **LATENT)
    try:
        stage_serve(ctx)
    finally:
        ctx.cfg = saved


def stage_serve_state(ctx: Ctx) -> None:
    """The serve stage once more, on the model that keeps recurrent
    state by slot beside its K/V pages: the scored completions (the
    plain forward pass starts every row from an empty state), no
    prefix cache and no hit, `/stats state_pool`, and no copy of the
    pages, the state or the tails in the compiled decode and
    prefill-chunk programs."""
    saved = ctx.cfg
    cfg = dict(saved, **STATE)
    if ctx.args.rehearse:
        # Off a TPU the decode read is the XLA gather.
        cfg['attention_impl'] = 'xla'
    ctx.cfg = cfg
    try:
        stage_serve(ctx)
    finally:
        ctx.cfg = saved


# -- main ---------------------------------------------------------------------
def main() -> int:
    global PREFIX
    parser = argparse.ArgumentParser(
        description='Prove the trainer and the serving engine on the '
                    'chip, through their normal entry points.')
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1)
    parser.add_argument('--rehearse', action='store_true',
                        help='CPU dress rehearsal at tiny presets '
                             '(JAX_PLATFORMS=cpu); every line is '
                             'marked [rehearsal] and proves nothing '
                             'about the chip')
    parser.add_argument('--seed', type=int, default=0,
                        help='prompts are generated from it')
    parser.add_argument('--deadline', type=float, default=1140.0,
                        help='seconds after which the run fails by '
                             'itself (the driver allows 1200)')
    parser.add_argument('--work-dir', default=None,
                        help='logs, checkpoints, orchestrator state '
                             '(default: chiprun_out/chip_smoke_<mode>; '
                             'emptied first)')
    args = parser.parse_args()
    if args.rehearse:
        PREFIX = '[rehearsal] '
    if not os.path.isdir(os.path.join(REPO, 'skypilot_tpu')):
        say(f'chip_smoke: FAILED — no skypilot_tpu package next to '
            f'{os.path.abspath(__file__)}: this script drives the '
            f'program, it is not the program')
        return 1

    sys.path.insert(0, REPO)
    from skypilot_tpu.utils import compile_cache  # imports no JAX
    mode = ('rehearse' if args.rehearse else 'tpu') + f'_{args.chips}'
    work = os.path.abspath(args.work_dir or os.path.join(
        REPO, 'chiprun_out', f'chip_smoke_{mode}'))
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    marker = f'{os.getpid()}-{int(time.time())}'
    env = dict(os.environ, CHIP_SMOKE_RUN=marker, PYTHONUNBUFFERED='1')
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    if args.rehearse:
        env['JAX_PLATFORMS'] = 'cpu'
        env['XLA_FLAGS'] = (
            env.get('XLA_FLAGS', '') + ' --xla_force_host_platform_'
            f'device_count={args.chips}').strip()
        # No persistent cache on the CPU: XLA:CPU entries are
        # machine-specific and a CPU compile is seconds.
        env.pop(compile_cache.ENV_VAR, None)
    else:
        # Every child shares one cache: the trainer's second start and
        # a second run of this script in the same place compile
        # nothing. Placed from outside when the variable is set.
        env.setdefault(compile_cache.ENV_VAR, compile_cache.default_dir())
    say(f'chip_smoke: mode {mode}, seed {args.seed}, work dir {work}, '
        f'compile cache {env.get(compile_cache.ENV_VAR)}')

    ctx = Ctx(args, work, env, time.monotonic() + args.deadline)
    stages = [stage_device, stage_train, stage_resume]
    if args.chips > 1:
        stages.append(stage_overlap)
    stages.append(stage_serve)
    if args.chips == 1:
        stages.append(stage_serve_latent)
        stages.append(stage_serve_state)
    t0 = time.monotonic()
    ok = False
    try:
        for stage in stages:
            t_stage = time.monotonic()
            stage(ctx)
            say(f'{stage.__name__[len("stage_"):]}: PASSED in '
                f'{time.monotonic() - t_stage:.1f}s')
        ok = True
    except SmokeFailure as e:
        say(f'chip_smoke: FAILED — {e}')
    finally:
        strays = kill_strays(marker)
        if strays:
            say(f'chip_smoke: killed leftover processes {strays}')
        # Logs and the metrics file stay for the post-mortem; the
        # checkpoints (GBs) and the orchestrator's state do not.
        for big in ('ckpt', 'sky_home'):
            shutil.rmtree(os.path.join(work, big), ignore_errors=True)
    if not ok:
        return 1
    say(f'chip_smoke: all stages passed in {time.monotonic() - t0:.1f}s')
    say(json.dumps({'ok': True, 'device': ctx.device}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
