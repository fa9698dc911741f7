"""Trainer step telemetry: one JSONL record per logged step window.

`train_lm.py --metrics-file out.jsonl` constructs a StepMetrics and
calls `log()` at every `--log-every` boundary. Each record carries
the TPU-pod vital signs (step time, tokens/s, loss, grad norm), where
the host spent the step (`data_s`, `dispatch_s`, `sync_s`, `ckpt_s`,
`other_s`: the loop's phases, summing to `step_time_s`) plus
an achieved-MFU estimate against the device's peak FLOPs — the
"are we running as fast as the hardware allows" number every perf PR
is judged by. Records are flushed line-by-line so a preempted run's
file is still valid JSONL up to the last completed window.

MFU model: achieved = 6 * n_params * tokens/s (the standard dense-
transformer train-FLOPs estimate, fwd+bwd) over the device's peak
from ONE table keyed by the `device_kind` the chip itself reports. A
TPU whose kind is not in the table is an error — a guessed peak
would put a made-up number under a device metric's name. Only a
non-TPU backend (the CPU tests) reports mfu = null.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

#: Peak bf16 FLOP/s per chip, keyed by `jax.devices()[0].device_kind`
#: exactly as the runtime prints it: a v5e chip says 'TPU v5 lite'
#: under libtpu 0.0.34 (my chip run, PR 21); JAX's own tables
#: (jax/_src/pallas/mosaic/tpu_info.py) accept both spellings per
#: generation, so both are listed. Source of the numbers: Google Cloud
#: TPU documentation, per-generation system-architecture pages.
#: bench.py reads the same table.
PEAK_BF16_FLOPS_BY_DEVICE_KIND: Dict[str, float] = {
    'TPU v2': 45e12,
    'TPU v3': 123e12,
    'TPU v4': 275e12,
    'TPU v5 lite': 197e12, 'TPU v5e': 197e12,
    'TPU v5': 459e12, 'TPU v5p': 459e12,
    'TPU v6 lite': 918e12, 'TPU v6e': 918e12,
}


def peak_flops_per_device() -> Optional[float]:
    """Peak bf16 FLOP/s of one device of the default backend: the
    table entry for its `device_kind`; None off TPU (there is no MFU
    to speak of); KeyError for a TPU the table does not know."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        return None
    try:
        return PEAK_BF16_FLOPS_BY_DEVICE_KIND[dev.device_kind]
    except KeyError:
        raise KeyError(
            f'no peak FLOP/s known for device_kind '
            f'{dev.device_kind!r}: add it, with its source, to '
            f'PEAK_BF16_FLOPS_BY_DEVICE_KIND (known: '
            f'{sorted(PEAK_BF16_FLOPS_BY_DEVICE_KIND)}) — an MFU '
            f'against an assumed peak is not a measurement') from None


class StepMetrics:
    """JSONL step-metrics emitter. Construct once per run; `log()`
    per logged window; `close()` at the end (also flushes)."""

    def __init__(self, path: str, *, n_params: Optional[int] = None,
                 n_devices: int = 1,
                 peak_flops: Optional[float] = None) -> None:
        self.path = os.path.expanduser(path)
        parent = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(parent, exist_ok=True)
        self.n_params = n_params
        self.n_devices = max(n_devices, 1)
        self.peak_flops = (peak_flops if peak_flops is not None
                           else peak_flops_per_device())
        self._f = open(self.path, 'a', encoding='utf-8')

    def mfu(self, tokens_per_sec: float) -> Optional[float]:
        """Achieved-MFU estimate: 6 * N * tok/s over the slice's
        aggregate peak. None without a param count or a known peak."""
        if not self.n_params or not self.peak_flops:
            return None
        achieved = 6.0 * self.n_params * tokens_per_sec
        return round(achieved / (self.peak_flops * self.n_devices), 4)

    def log(self, step: int, *, step_time_s: float, tokens: int,
            loss: float, grad_norm: Optional[float] = None,
            bubble_frac: Optional[float] = None,
            collective_wait_s: Optional[float] = None,
            phase_s: Optional[Dict[str, float]] = None,
            extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Write one record covering a window that ended at `step`:
        `step_time_s` is the mean per-step wall time over the window,
        `tokens` the tokens consumed by ONE step. `bubble_frac` is
        the pipeline schedule's idle fraction (null for non-pipeline
        runs); `collective_wait_s` the host-observed drain wait at
        the window boundary — the un-overlapped remainder of the
        device critical path the --overlap knob exists to shrink.
        `phase_s` is the trainer loop's phases over the window
        (`data_s`, `dispatch_s`, `sync_s`, `ckpt_s`: seconds per
        step, like `step_time_s`); the record adds `other_s`, the
        rest of the loop body, so that the five sum to
        `step_time_s`."""
        tokens_per_sec = (tokens / step_time_s if step_time_s > 0
                          else 0.0)
        record: Dict[str, Any] = {
            'step': int(step),
            'time': time.time(),
            'step_time_s': round(float(step_time_s), 6),
            'tokens_per_sec': round(tokens_per_sec, 2),
            'loss': float(loss),
            'grad_norm': (None if grad_norm is None
                          else float(grad_norm)),
            'mfu': self.mfu(tokens_per_sec),
            'bubble_frac': (None if bubble_frac is None
                            else round(float(bubble_frac), 6)),
            'collective_wait_s': (
                None if collective_wait_s is None
                else round(float(collective_wait_s), 6)),
        }
        if phase_s is not None:
            record.update({k: round(float(v), 6)
                           for k, v in phase_s.items()})
            record['other_s'] = round(
                float(step_time_s) - sum(phase_s.values()), 6)
        if extra:
            record.update(extra)
        self._f.write(json.dumps(record) + '\n')
        self._f.flush()
        return record

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self) -> 'StepMetrics':
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse a --metrics-file back into records (analysis + tests)."""
    records = []
    with open(os.path.expanduser(path), 'r', encoding='utf-8') as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records
