#!/usr/bin/env python3
"""The benchmark's one command.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Reads the cell from BENCHMARK.json, its configuration from
perfbench/configs/, its traffic mix from perfbench/mixes/ and hands
both to the driver the mix names (perfbench/drivers/). The driver runs
the program through the entry point its users call, on the machine this
was started on; no TPU, or fewer chips than the cell asks for, is a
non-zero exit with no result line. The LAST line of stdout is the
result: one JSON object (see perfbench/README.md).

    --rehearse      CPU dress rehearsal at the tiny presets; every line,
                    the last too, is marked `[rehearsal]` and is no
                    measurement
    --rate R        serving cells: offer R requests/s instead of the
                    mix's rate (the knee sweep); the result is marked
                    `"sweep": true`
    --control       serving cells: after the scoring, also read the
                    control (the reference in the next lower precision)
                    on the same rows; an earlier line, no metric
    --work-dir DIR  keep the run's files (records, metrics, trace) in
                    DIR instead of a temporary directory
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--seconds', type=float, default=None)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    parser.add_argument('--rehearse', action='store_true')
    parser.add_argument('--rate', type=float, default=None)
    parser.add_argument('--control', action='store_true')
    parser.add_argument('--work-dir', default=None)
    args = parser.parse_args()

    if not os.path.isdir(os.path.join(ROOT, 'skypilot_tpu')):
        print(f'perfbench: FAILED - no skypilot_tpu package in {ROOT}: '
              f'the benchmark drives the program, it is not the program',
              file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)
    from perfbench import harness
    from perfbench import manifest as manifest_lib
    try:
        manifest = manifest_lib.load()
        cell = manifest_lib.cell(manifest, args.workload)
        config = manifest_lib.config(manifest, cell['config'])
        mix = manifest_lib.mix(cell['traffic'])
        driver = manifest_lib.driver(mix['driver'])
    except manifest_lib.ManifestError as e:
        print(f'perfbench: FAILED - {e}', file=sys.stderr)
        return 1
    if args.seconds is None:
        args.seconds = float(manifest['run_seconds'])
    ctx = harness.Ctx(args=args, manifest=manifest, cell=cell,
                      config=config, mix=mix, t_start=T_START,
                      work=harness.make_work_dir(args))
    ctx.say(f'cell {cell["name"]} (config {cell["config"]}, mix '
            f'{cell["traffic"]}, driver {mix["driver"]}), seed '
            f'{args.seed}, window {args.seconds:g}s, trace {args.trace}'
            + (f', SWEEP at {args.rate:g} requests/s'
               if args.rate is not None else ''))
    driver.run(ctx)
    # A driver ends the process itself; coming back is a failure.
    harness.die(ctx, 'the driver returned without a result')
    return 1


if __name__ == '__main__':
    sys.exit(main())
