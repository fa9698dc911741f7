"""Measure the pallas-flash vs XLA attention crossover on the chip.

`_FLASH_MIN_SEQ = 2048` in ops/attention.py is a guess: where the
Pallas flash kernel starts to beat XLA's fused attention on a v5e is
NOT MEASURED (ROADMAP S2). This script times a fwd+bwd
GPT-2-block-shaped attention at several sequence lengths with flash
forced ON and OFF and prints the winner per length, so
`_FLASH_MIN_SEQ` can be set from data:

    python benchmarks/flash_crossover.py            # on the TPU
    python benchmarks/flash_crossover.py --cpu      # smoke the harness
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--cpu', action='store_true')
    parser.add_argument('--batch', type=int, default=8)
    parser.add_argument('--heads', type=int, default=12)
    parser.add_argument('--head-dim', type=int, default=64)
    parser.add_argument('--seqs', type=int, nargs='+',
                        default=[1024, 2048, 4096, 8192])
    parser.add_argument('--steps', type=int, default=10)
    args = parser.parse_args()

    import jax
    if args.cpu:
        jax.config.update('jax_platforms', 'cpu')
    import jax.numpy as jnp

    results = []
    for seq in args.seqs:
        row = {'seq': seq}
        for mode, min_seq in (('xla', 1 << 30), ('flash', 1)):
            os.environ['SKYPILOT_TPU_FLASH_MIN_SEQ'] = str(min_seq)
            # Re-import so the module-level constant re-reads the env.
            for name in list(sys.modules):
                if name.startswith('skypilot_tpu.ops'):
                    del sys.modules[name]
            from skypilot_tpu.ops import attention as attn

            def loss_fn(q, k, v):
                out = attn.dot_product_attention(q, k, v, causal=True)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            step = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))
            shape = (args.batch, seq, args.heads, args.head_dim)
            key = jax.random.PRNGKey(0)
            q = jax.random.normal(key, shape, jnp.bfloat16)
            k = jax.random.normal(key, shape, jnp.bfloat16)
            v = jax.random.normal(key, shape, jnp.bfloat16)
            try:
                out = step(q, k, v)           # compile + correctness
                jax.block_until_ready(out)
                start = time.perf_counter()
                for _ in range(args.steps):
                    out = step(q, k, v)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - start) / args.steps * 1e3
            except Exception as e:  # pylint: disable=broad-except
                print(f'# seq={seq} {mode}: failed '
                      f'({type(e).__name__}: {str(e)[:120]})')
                ms = float('inf')
            row[mode] = ms
            print(f'# seq={seq:5d} {mode:5s}: {ms:8.2f} ms/step (fwd+bwd)',
                  flush=True)
        winner = 'flash' if row['flash'] < row['xla'] else 'xla'
        speedup = (row['xla'] / row['flash']
                   if row['flash'] not in (0, float('inf')) else 0)
        row['winner'] = winner
        results.append(row)
        print(f'= seq={seq}: {winner} wins '
              f'(flash is {speedup:.2f}x vs xla)', flush=True)

    flash_wins = [r['seq'] for r in results if r['winner'] == 'flash']
    if flash_wins:
        print(f'=> set SKYPILOT_TPU_FLASH_MIN_SEQ={min(flash_wins)} '
              f'(ops/attention.py _FLASH_MIN_SEQ)')
    else:
        print('=> XLA fused attention wins at every measured length; '
              'keep _FLASH_MIN_SEQ high (pallas kernel needs tuning '
              'before it pays off here)')


if __name__ == '__main__':
    main()
