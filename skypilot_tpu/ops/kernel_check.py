"""Compile every Pallas call the TPU path can reach, ON THE CHIP, and
compare it with its XLA reference.

    python -m skypilot_tpu.ops.kernel_check [--out PATH] [--seed N]

The CPU tests run these kernels with `interpret=True`, which says
nothing about what Mosaic accepts: block shapes, tile alignment,
scalar-prefetch reads and VMEM limits are only checked by the real
compiler. This runs each call at Llama-3-8B serving shapes (32 query /
8 KV heads of 128, page 16, 128 pages per sequence, batch 16) through
the same wrappers the models call (a kernel no route reaches on its
own, by its own name), compiled, and reports per case either `ok`
with the largest error against the reference and the compiled call's
run time (`run_us`: a call of twenty enqueued back to back, the
median of five such batches), `mismatch` (also: the wrapper took
another route than the case names), or `refused` with the compiler's
own message.
The `.../32rows` cases run the in-repo decode read at the benchmark
cells' 32 slots (every fourth of length 0), alone and under a tensor
mesh of the host's chips. `xla_paged_attention/bf16/D=64/S=1` is the
read a pool of GPT-2's 64-wide heads takes, which no Pallas kernel
here compiles: the XLA gather, compiled for the chip and held to the
same reference in float32. The `sparse_latent/...` cases are the three
device paths of a latent page pool (DeepSeek-V3.2's widths, the
benchmark cell's 48 slots, two in three of length 0, and 512-token
chunks), each held to the plain-XLA path in float32: the decode
round's two reads are plain XLA themselves, a chunk's attention is
the kernel of ops/pallas_latent.py (at two depths, 12 and 28 blocks
of keys, so that the time a block is on record). The `ssm/...` cases
are ops/ssm.py at Nemotron 3 Super's widths (128 heads of 64, state
128, 8 groups): a decode round's step over the cell's 128 slots with a
third and all of the lanes live, through the kernel of
ops/pallas_ssm.py (route `state_pallas`), the state and the tails
donated from call to call so that the update in place is what is
timed, held to the float32 recurrence step by step and to the live
rows' loop, with the loop's own run time (`loop_us`); and a 512-token
prefill chunk against the recurrence. The `experts/...` cases are one
routed layer's held experts through the kernel of ops/pallas_experts.py
at the two routed cells' published widths (Nemotron 3 Super's 128
`relu2` experts of 1,024 x 2,688, DeepSeek-V3.2's 16 `swiglu` experts
of 7,168 x 2,048), a decode round and a 512-token chunk each, against
the `lax.switch` loop on the operands in float32, with the bf16 loop's
own run time (`loop_us`), and the 64 `swiglu` experts of 2,048 x
1,536 of LFM2-24B-A2B at its 128 slots and 256-token chunks. The
`packed_paged_attention/...` cases are the decode read of a pool of
64-wide heads packed a token a row (LFM2's 8 K/V heads in one 512-wide
row) at that cell's 128 slots of 2,560 tokens, against the gather
reference of the pool unpacked: the XLA gather of every row's whole
table by name, and the kernel of ops/pallas_packed.py (route
`packed_decode`) at the cell's round (32 rows of about 510 tokens live,
96 of length 0) and with every row live. The `streamed_matmul/...` cases
are ops/pallas_streamed at LFM2's two conv projections for a decode
round of 128 slots, the matrix read from HBM inside the call, against
the float32 product; a call is shorter than the host's enqueue of it,
so their `run_us` is the enqueue's. It exits non-zero when any case
is not `ok`, and when the backend is not a TPU: a CPU run of this file
would check nothing.

Tolerances are set from the dtype: operands are bf16 (8 mantissa
bits, eps 2^-8 ~ 4e-3) and both sides accumulate in f32, so outputs
of magnitude <= 1 must agree to 2e-2 absolute + 2e-2 relative; a
kernel that dropped the softmax scale, a mask or a page would be off
by O(1). References run under `default_matmul_precision('highest')`:
a TPU's default f32 matmul is itself bf16-pass.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import traceback
import zlib
from typing import Any, Callable, Dict, List

ATOL = RTOL = 2e-2
RUNS = 20          # calls a timed batch

# Llama-3-8B attention geometry and the serving page geometry
# (chip_smoke.py serves the same: --max-total-len 2048, page 16).
Q_HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128
PAGE, PAGES_PER_SEQ, BATCH = 16, 128, 16
D_MODEL, LORA_RANK, LORA_SLOTS = 4096, 16, 8


def _pool(key, quantized: bool, batch: int = BATCH,
          kv_heads: int = KV_HEADS, head_dim: int = HEAD_DIM):
    """(k_pages, v_pages, k_scales, v_scales, page_indices): a pool in
    which every sequence owns distinct pages, as the allocator hands
    them out (page 0 is the engine's trash page)."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.ops import paged_attention as paged_ops
    total_pages = batch * PAGES_PER_SEQ + 1
    kk, kv, kp = jax.random.split(key, 3)
    shape = (total_pages, PAGE, kv_heads, head_dim)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    perm = jax.random.permutation(kp, total_pages - 1) + 1
    page_indices = perm.reshape(batch, PAGES_PER_SEQ).astype(jnp.int32)
    to_pool = lambda x: jnp.transpose(x, (2, 0, 1, 3))  # noqa: E731
    if not quantized:
        return to_pool(k), to_pool(v), None, None, page_indices
    qk, sk = paged_ops.quantize_kv_rows(k)
    qv, sv = paged_ops.quantize_kv_rows(v)
    return to_pool(qk), to_pool(qv), sk, sv, page_indices


def _wrong_route(want: str, got: str) -> Dict[str, Any]:
    return {'verdict': 'mismatch', 'detail':
            f'the wrapper resolves this read to {got!r}, not {want!r}'}


def _paged_decode(route: str, quantized: bool, batch: int = BATCH,
                  dead_every: int = 0, over_tensor_mesh: bool = False,
                  heads: tuple = (Q_HEADS, KV_HEADS, HEAD_DIM)
                  ) -> Callable[[Any], Dict]:
    """The S=1 read through `paged_decode_attention`, which must take
    `route` for this pool; 'fused' of an unquantized pool is reached
    by no route and is called by name.
    `dead_every` n: every n-th row has length 0 (a lane that is
    not decoding); such rows are compared as zeros, since the
    reference's softmax over no token is not a number.
    `over_tensor_mesh`: pool and heads sharded over all the host's
    chips (a one-chip host runs the same case unsharded)."""
    q_heads, kv_heads, head_dim = heads

    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.ops import paged_attention as paged_ops
        from skypilot_tpu.ops import pallas_paged
        kq, kl, kpool = jax.random.split(key, 3)
        k_pages, v_pages, ks, vs, tbl = _pool(kpool, quantized, batch,
                                              kv_heads, head_dim)
        q = jax.random.normal(kq, (batch, q_heads, head_dim),
                              jnp.bfloat16)
        lengths = jax.random.randint(
            kl, (batch,), 1, PAGE * PAGES_PER_SEQ + 1, jnp.int32)
        if dead_every:
            lengths = jnp.where(
                jnp.arange(batch) % dead_every == 0, 0, lengths)
        if route == 'fused' and not quantized:
            def read(q, k_pages, v_pages, lengths, tbl, **scales):
                return pallas_paged.fused_paged_attention(
                    q[:, None], k_pages, v_pages,
                    (lengths - 1)[:, None], tbl, **scales)[:, 0]
        else:
            read = paged_ops.paged_decode_attention
            got = pallas_paged.resolve_impl(quantized=quantized,
                                            decode_pool=k_pages)
            if got != route:
                return _wrong_route(route, got)

        def run(fn):
            return jax.jit(lambda *a: jnp.where(
                (a[3] > 0)[:, None, None],
                fn(*a, k_scales=ks, v_scales=vs), 0))
        reference = run(paged_ops._reference_paged_attention)
        args = (q, k_pages, v_pages, lengths, tbl)
        if not over_tensor_mesh:
            return _compare(run(read), reference, args)
        # `--tensor N` as the server runs it: kv heads (and their
        # query groups) over every chip of the host, the call under
        # the mesh context so that it is shard-mapped.
        from jax.sharding import NamedSharding, PartitionSpec as P
        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.make_mesh(
            mesh_lib.MeshConfig(tensor=jax.device_count()))
        pool = NamedSharding(mesh, P('tensor'))
        args = (jax.device_put(q, NamedSharding(
                    mesh, P(None, 'tensor', None))),
                jax.device_put(k_pages, pool),
                jax.device_put(v_pages, pool), lengths, tbl)
        with mesh:
            res = _compare(run(read), reference, args)
            hlo = run(read).lower(*args).compile().as_text()
        if 'all-gather' in hlo or 'all-to-all' in hlo:
            res.update(verdict='mismatch', detail='the compiled call '
                       'moves the head-sharded pool between chips')
        return res
    return case


def _paged_chunk(quantized: bool, chunk: int, batch: int
                 ) -> Callable[[Any], Dict]:
    """The S>1 read of the fused kernel: through
    `paged_chunk_attention` for an int8 pool (which must take 'fused'
    there), by name for an unquantized one (whose chunks take the
    gather)."""
    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.ops import paged_attention as paged_ops
        from skypilot_tpu.ops import pallas_paged
        kq, ko, kpool = jax.random.split(key, 3)
        k_pages, v_pages, ks, vs, tbl = _pool(kpool, quantized)
        tbl = tbl[:batch]
        q = jax.random.normal(kq, (batch, chunk, Q_HEADS, HEAD_DIM),
                              jnp.bfloat16)
        # A chunk at a ragged per-row offset into the history, the
        # suffix-prefill / verify-chunk shape.
        offset = jax.random.randint(
            ko, (batch, 1), 0, PAGE * PAGES_PER_SEQ - chunk, jnp.int32)
        positions = offset + jnp.arange(chunk, dtype=jnp.int32)[None]
        if quantized:
            got = pallas_paged.resolve_impl(quantized=True)
            if got != 'fused':
                return _wrong_route('fused', got)
            read = paged_ops.paged_chunk_attention
        else:
            read = pallas_paged.fused_paged_attention

        def run(fn):
            return jax.jit(lambda *a: fn(*a, k_scales=ks, v_scales=vs))
        args = (q, k_pages, v_pages, positions, tbl)
        return _compare(run(read),
                        run(paged_ops._reference_chunk_attention), args)
    return case


def _qkv_lora(chunk: int, batch: int) -> Callable[[Any], Dict]:
    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.models import lora as lora_lib
        from skypilot_tpu.ops import pallas_paged
        keys = jax.random.split(key, 8)
        x = jax.random.normal(keys[0], (batch, chunk, D_MODEL),
                              jnp.bfloat16)
        ids = jax.random.randint(keys[1], (batch,), 0, LORA_SLOTS,
                                 jnp.int32)
        factors = []
        for i, d_out in enumerate((Q_HEADS * HEAD_DIM,
                                   KV_HEADS * HEAD_DIM,
                                   KV_HEADS * HEAD_DIM)):
            factors.append({
                'a': 0.02 * jax.random.normal(
                    keys[2 + 2 * i], (LORA_SLOTS, D_MODEL, LORA_RANK),
                    jnp.bfloat16),
                'b': 0.02 * jax.random.normal(
                    keys[3 + 2 * i], (LORA_SLOTS, LORA_RANK, d_out),
                    jnp.bfloat16)})

        @jax.jit
        def fused(x, ids, *fs):
            return pallas_paged.fused_qkv_lora_delta(x, *fs, ids)

        @jax.jit
        def ref(x, ids, *fs):
            return tuple(
                lora_lib.apply_delta(
                    jnp.zeros(x.shape[:2] + (f['b'].shape[-1],),
                              jnp.float32), x, f, ids, 1.0)
                for f in fs)
        return _compare(fused, ref, (x, ids, *factors))
    return case


def _flash(batch: int, heads: int, kv_heads: int, head_dim: int,
           seq: int = 2048) -> Callable[[Any], Dict]:
    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.ops import attention as attention_ops
        kq, kk, kv, kc = jax.random.split(key, 4)
        q = jax.random.normal(kq, (batch, seq, heads, head_dim),
                              jnp.bfloat16)
        k = jax.random.normal(kk, (batch, seq, kv_heads, head_dim),
                              jnp.bfloat16)
        v = jax.random.normal(kv, (batch, seq, kv_heads, head_dim),
                              jnp.bfloat16)
        cot = jax.random.normal(kc, q.shape, jnp.bfloat16)

        def run(impl):
            def loss(q, k, v):
                out = attention_ops.dot_product_attention(
                    q, k, v, causal=True, impl=impl)
                return jnp.sum(out.astype(jnp.float32) *
                               cot.astype(jnp.float32)), out
            # Forward output and all three gradients: the backward
            # kernels are separate Pallas calls.
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                              has_aux=True))
        return _compare(run('flash'), run('xla'), (q, k, v),
                        # dq/dk/dv sum ~S terms of O(1): scale the
                        # tolerance by the reference's own magnitude.
                        relative_to_max=True)
    return case


# DeepSeek-V3.2's latent page layout at the benchmark cell's shapes:
# 128 heads over a row of 512 + 64 values in 640, 64 index heads of 128, the 2,048
# best of up to 16,384 positions, 48 slots of which every third holds a
# request (the others have length 0), 512-token chunks.
V32_HEADS, V32_RANK, V32_ROW = 128, 512, 640   # 512 + 64 in 640
V32_INDEX_HEADS, V32_INDEX_DIM, V32_TOPK = 64, 128, 2048
V32_PAGES_PER_SEQ, V32_BATCH, V32_CHUNK = 1024, 48, 512


def _sparse_latent(path: str, offset: int = 5632
                   ) -> Callable[[Any], Dict]:
    """One of ops/sparse_latent.py's three device paths (a latent
    pool: bf16 latent rows, float32 index queries and keys) against
    the plain-XLA path on the operands in float32 at `highest`
    precision:
    'index' the decode round's index scores over the paged keys,
    'attend' its selection and the attention over the selected rows
    (both sides select on the same float32 scores; these two are
    route 'sparse_latent_xla'), 'chunk' a 512-token prefill chunk
    `offset` tokens into its context, whose attention must take the
    kernel of ops/pallas_latent.py ('sparse_latent_pallas') and is
    held to the XLA walk."""

    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.ops import pallas_paged, sparse_latent
        got = pallas_paged.resolve_impl(layout='latent')
        if got != 'sparse_latent_xla':
            return _wrong_route('sparse_latent_xla', got)
        batch = 1 if path == 'chunk' else V32_BATCH
        total = batch * V32_PAGES_PER_SEQ + 1
        keys = jax.random.split(key, 8)
        bf16 = jnp.bfloat16
        latent = jax.random.normal(
            keys[0], (1, total, PAGE, V32_ROW), bf16)
        index_k = jax.random.normal(           # float32, as cached
            keys[1], (1, total, PAGE, V32_INDEX_DIM), jnp.float32)
        tbl = (jax.random.permutation(keys[2], total - 1) + 1).reshape(
            batch, V32_PAGES_PER_SEQ).astype(jnp.int32)
        scale = 192 ** -0.5
        f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
        if path == 'chunk':
            q = jax.random.normal(
                keys[3], (1, V32_CHUNK, V32_HEADS, V32_ROW),
                bf16)
            q_idx = jax.random.normal(
                keys[4], (1, V32_CHUNK, V32_INDEX_HEADS, V32_INDEX_DIM),
                jnp.float32)
            w_idx = jax.random.normal(
                keys[5], (1, V32_CHUNK, V32_INDEX_HEADS), jnp.float32)
            positions = (offset + jnp.arange(V32_CHUNK))[None]
            got = sparse_latent.chunk_route(q, latent, V32_PAGES_PER_SEQ,
                                            V32_RANK)
            if got != 'sparse_latent_pallas':
                return _wrong_route('sparse_latent_pallas', got)

            def chunk(q, q_idx, w_idx, latent, index_k, positions, tbl,
                      route=None):
                return sparse_latent.sparse_latent_chunk(
                    q, q_idx, w_idx, latent, index_k, positions, tbl,
                    topk=V32_TOPK, scale=scale, value_dim=V32_RANK,
                    route=route)

            def reference(q, q_idx, w_idx, latent, index_k, *rest):
                return chunk(*f32(q, q_idx), w_idx,
                             *f32(latent, index_k), *rest,
                             route='sparse_latent_xla')

            return _compare(jax.jit(chunk), reference,
                            (q, q_idx, w_idx, latent, index_k, positions,
                             tbl))
        lengths = jnp.where(
            jnp.arange(batch) % 3 == 0,
            jax.random.randint(keys[3], (batch,), V32_TOPK,
                               PAGE * V32_PAGES_PER_SEQ + 1, jnp.int32), 0)
        q_idx = jax.random.normal(
            keys[4], (batch, V32_INDEX_HEADS, V32_INDEX_DIM), jnp.float32)
        w_idx = jax.random.normal(keys[5], (batch, V32_INDEX_HEADS),
                                  jnp.float32)
        if path == 'index':
            def scores(q_idx, w_idx, index_k, tbl, lengths):
                s = sparse_latent.index_scores_decode(
                    q_idx, w_idx, index_k, tbl, lengths)
                return jnp.where(jnp.isfinite(s), s, 0.0)

            def reference(q_idx, w_idx, index_k, tbl, lengths):
                return scores(*f32(q_idx), w_idx, *f32(index_k), tbl,
                              lengths)

            return _compare(jax.jit(scores), reference,
                            (q_idx, w_idx, index_k, tbl, lengths),
                            relative_to_max=True)
        q = jax.random.normal(
            keys[6], (batch, V32_HEADS, V32_ROW), bf16)
        picks = jnp.where(
            jnp.arange(PAGE * V32_PAGES_PER_SEQ)[None] < lengths[:, None],
            jax.random.normal(keys[7], (batch, PAGE * V32_PAGES_PER_SEQ)),
            -jnp.inf)

        def attend(q, latent, tbl, picks):
            idx, valid = sparse_latent.select_topk(picks, V32_TOPK)
            return sparse_latent.sparse_latent_decode(
                q, latent, tbl, idx, valid, scale=scale,
                value_dim=V32_RANK)

        def reference(q, latent, tbl, picks):
            return attend(*f32(q, latent), tbl, picks)

        return _compare(jax.jit(attend), reference,
                        (q, latent, tbl, picks))
    return case


# Nemotron 3 Super's Mamba-2 mixer and the benchmark cell's 128 slots.
SSM_HEADS, SSM_HEAD_DIM, SSM_STATE, SSM_GROUPS, SSM_TAPS = 128, 64, 128, 8, 4
SSM_SLOTS, SSM_CHUNK, SSM_SUB = 128, 512, 128


def _ssm(path: str, live_lanes: int = 0) -> Callable[[Any], Dict]:
    """ops/ssm.py at the published widths against the float32
    recurrence step by step (`ssm_reference`): 'update' a decode
    round's step over 128 slots of which `live_lanes` are live, by the
    route a TPU takes ('state_pallas') and also held to the loop
    ('live_rows_loop', timed as `loop_us`; the state and the tails
    donated and handed from call to call, as the engine does: what is
    timed is the update in place), 'scan' a 512-token prefill chunk
    from a random state."""

    def case(key):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from skypilot_tpu.ops import ssm
        heads, hd, n, g = SSM_HEADS, SSM_HEAD_DIM, SSM_STATE, SSM_GROUPS
        inner, bc = heads * hd, g * n
        width = inner + 2 * bc
        keys = jax.random.split(key, 10)
        a = -jnp.exp(jnp.log(jax.random.uniform(keys[0], (heads,),
                                                minval=1.0, maxval=16.0)))
        d = jax.random.normal(keys[1], (heads,))
        if path == 'scan':
            x = jax.random.normal(keys[2], (1, SSM_CHUNK, heads, hd),
                                  jnp.bfloat16)
            b = jax.random.normal(keys[3], (1, SSM_CHUNK, g, n),
                                  jnp.bfloat16)
            c = jax.random.normal(keys[4], (1, SSM_CHUNK, g, n),
                                  jnp.bfloat16)
            dt = jax.nn.softplus(
                jax.random.normal(keys[5], (1, SSM_CHUNK, heads)) - 3.0)
            state = jax.random.normal(keys[6], (1, heads, hd, n))
            lengths = jnp.asarray([SSM_CHUNK - 37], jnp.int32)

            def scan(x, dt, b, c, state, lengths):
                y, h = ssm.ssm_scan(x, dt, a, b, c, d, state, lengths,
                                    SSM_SUB)
                valid = jnp.arange(SSM_CHUNK)[None, :] < lengths[:, None]
                return jnp.where(valid[..., None, None], y, 0.0), h

            def reference(x, dt, b, c, state, lengths):
                y, h = ssm.ssm_reference(x, dt, a, b, c, d, state, lengths)
                valid = jnp.arange(SSM_CHUNK)[None, :] < lengths[:, None]
                return jnp.where(valid[..., None, None], y, 0.0), h

            return _compare(jax.jit(scan), reference,
                            (x, dt, b, c, state, lengths),
                            relative_to_max=True)

        from skypilot_tpu.ops import pallas_ssm
        got = pallas_ssm.resolve_route(heads, hd, n, g, width, SSM_TAPS,
                                       jnp.bfloat16, SSM_SLOTS)
        if got != 'state_pallas':
            return _wrong_route('state_pallas', got)
        slots = SSM_SLOTS
        state = jax.random.normal(keys[2], (slots, heads, hd, n))
        tail = jax.random.normal(keys[3], (slots, SSM_TAPS - 1, width),
                                 jnp.bfloat16)
        xbc = jax.random.normal(keys[4], (slots, width), jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(keys[5], (slots, heads)) - 3.0)
        weight = jax.random.uniform(keys[6], (SSM_TAPS, width),
                                    minval=-0.5, maxval=0.5)
        bias = jax.random.uniform(keys[7], (width,), minval=-0.5,
                                  maxval=0.5)
        live = jax.random.permutation(
            keys[8], jnp.arange(slots) < live_lanes)

        with jax.default_matmul_precision('highest'):
            conv, _ = ssm.causal_conv(xbc[:, None], tail, weight, bias,
                                      jnp.ones((slots,), jnp.int32))
            act = jax.nn.silu(conv).astype(jnp.bfloat16)
            y_ref, h_ref = jax.jit(ssm.ssm_reference)(
                act[..., :inner].reshape(slots, 1, heads, hd), dt[:, None],
                a, act[..., inner:inner + bc].reshape(slots, 1, g, n),
                act[..., inner + bc:].reshape(slots, 1, g, n), d, state,
                jnp.ones((slots,), jnp.int32))
            keep = np.asarray(live)
            want_y = np.where(keep[:, None, None], np.asarray(y_ref[:, 0]),
                              0.0)
            want_h = np.where(keep[:, None, None, None], np.asarray(h_ref),
                              np.asarray(state))
        # The rows in the slot's layout, as the engine keeps them.
        state = ssm.state_to_slot(state)
        tail = ssm.tail_to_slot(tail, heads)

        def timed(route):
            """(y, state, tail) of the first call of `route` on the
            operands, as host arrays; its compile seconds and run_us,
            the state and the tails donated from call to call."""
            compiled = jax.jit(functools.partial(
                ssm.ssm_update, a=a, d=d, weight=weight, bias=bias,
                groups=g, route=route), donate_argnums=(0, 1))
            s, t = state + 0, tail + 0        # its own, to give away
            t0 = time.perf_counter()
            compiled = compiled.lower(s, t, xbc, dt, live=live).compile()
            compile_s = time.perf_counter() - t0
            y, s, t = compiled(s, t, xbc, dt, live=live)
            first = (np.asarray(y), np.asarray(ssm.state_from_slot(s)),
                     np.asarray(t, np.float32))
            batches = []
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(RUNS):
                    y, s, t = compiled(s, t, xbc, dt, live=live)
                jax.block_until_ready(y)
                batches.append((time.perf_counter() - t0) / RUNS)
            return first, compile_s, sorted(batches)[2] * 1e6

        (y, h, new), compile_s, run_us = timed('state_pallas')
        loop, _, loop_us = timed('live_rows_loop')
        scale = max(1.0, float(np.max(np.abs(want_y))))
        scale_h = max(1.0, float(np.max(np.abs(want_h))))
        err_y = float(np.max(np.abs(y - want_y)))
        err_h = float(np.max(np.abs(h - want_h)))
        # Against the loop: the same arithmetic, the same tails.
        loop_err = max(float(np.max(np.abs(y - loop[0]))) / scale,
                       float(np.max(np.abs(h - loop[1]))) / scale_h,
                       float(np.max(np.abs(new - loop[2]))))
        ok = (err_y <= ATOL * scale and err_h <= ATOL * scale_h
              and loop_err <= ATOL)
        return {'verdict': 'ok' if ok else 'mismatch',
                'max_abs_err': round(max(err_y / scale, err_h / scale_h), 6),
                'loop_max_abs_err': round(loop_err, 6),
                'compile_s': round(compile_s, 2),
                'run_us': round(run_us, 1),
                'loop_us': round(loop_us, 1),
                'live_lanes': int(live_lanes)}
    return case


# The held experts of the two routed cells, at their published widths:
# (activation, experts held, experts routed over, chosen a token, rows
# of `dim`, expert width, tokens of a decode round and of a chunk).
EXPERTS = {
    'relu2': ('relu2', 128, 512, 22, 1024, 2688, 128, 512),   # Nemotron 3
    'swiglu': ('swiglu', 16, 256, 8, 7168, 2048, 48, 512),    # DeepSeek-V3.2
    'lfm2': ('swiglu', 64, 64, 4, 2048, 1536, 128, 256),      # LFM2-24B-A2B
}


def _experts(cell: str, phase: str) -> Callable[[Any], Dict]:
    """One routed layer's grouped expert pass (ops/pallas_experts.py,
    route 'grouped_pallas') at a cell's published widths, against the
    `lax.switch` loop it replaces (models/deepseek.grouped_experts) on
    the operands in float32: `cell` names the widths (`EXPERTS`);
    'decode' a round of the cell's slots, a third of them live; 'chunk'
    a prefill chunk of the cell's size. Each token
    picks its experts uniformly among all the router's; those this
    chip holds are sorted as `MoEByShare` sorts them. Also times the
    loop itself on the bf16 operands (`loop_us`)."""

    def case(key):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from skypilot_tpu.models import deepseek as ds
        from skypilot_tpu.ops import pallas_experts
        act, held, routed, top, dim, width, slots, chunk = EXPERTS[cell]
        n_mat = pallas_experts.ACTS[act]
        got = pallas_experts.resolve_route(dim, width, n_mat, jnp.bfloat16)
        if got != 'grouped_pallas':
            return _wrong_route('grouped_pallas', got)
        tokens = slots if phase == 'decode' else chunk
        keys = jax.random.split(key, 3 + held)
        rng = np.random.default_rng(int(jax.random.randint(
            keys[0], (), 0, 1 << 30)))
        chosen = np.stack([rng.choice(routed, top, replace=False)
                           for _ in range(tokens)])
        live = (np.arange(tokens) % 3 == 0 if phase == 'decode'
                else np.ones(tokens, bool))
        here = (chosen < held) & live[:, None]
        local = np.where(here, chosen, held).reshape(-1)
        order = np.argsort(local, kind='stable')
        counts = jnp.asarray(np.bincount(local, minlength=held + 1)[:held],
                             jnp.int32)
        x = jax.random.normal(keys[1], (tokens, dim), jnp.bfloat16)
        x_sorted = x[jnp.asarray(order // top)]
        experts = tuple(tuple(
            (0.02 * jax.random.normal(
                jax.random.fold_in(keys[3 + e], j),
                (width, dim) if j == n_mat - 1 else (dim, width))
             ).astype(jnp.bfloat16) for j in range(n_mat))
            for e in range(held))
        fn = ds.EXPERT_FNS[act][0]
        row_block = min(tokens, ds.EXPERT_ROW_BLOCK)

        def loop(x_sorted, counts, experts):
            return ds.grouped_experts(x_sorted, counts, experts, row_block,
                                      fn)

        def reference(x_sorted, counts, experts):
            return loop(x_sorted.astype(jnp.float32), counts,
                        jax.tree.map(lambda w: w.astype(jnp.float32),
                                     experts))

        args = (x_sorted, counts, experts)
        res = _compare(jax.jit(functools.partial(
            pallas_experts.grouped_experts_pallas, act=act)), reference,
            args, relative_to_max=True)
        compiled = jax.jit(loop).lower(*args).compile()
        jax.block_until_ready(compiled(*args))
        batches = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(RUNS):
                out = compiled(*args)
            jax.block_until_ready(out)
            batches.append((time.perf_counter() - t0) / RUNS)
        res.update(loop_us=round(sorted(batches)[2] * 1e6, 1),
                   rows=int(x_sorted.shape[0]),
                   rows_held=int(counts.sum()),
                   experts_touched=int((counts > 0).sum()))
        return res
    return case


def _packed_decode(route: str, batch: int = 128, live_every: int = 1,
                   heads: tuple = (32, 8, 64), pages_per_seq: int = 160
                   ) -> Callable[[Any], Dict]:
    """The decode read of a pool of 64-wide heads packed a token a row
    (ops/paged_attention.packed_kv_layout): `lfm2-24b-a2b-l10`'s 32
    query and 8 K/V heads of 64 at its 128 slots of 2,560 tokens, held
    to the gather reference of the same pool unpacked ([8, pages, page,
    64]) in float32, 16 rows at a time. Route 'packed_decode' is the
    model's call, `packed_decode_attention`, which on a TPU is the
    kernel of ops/pallas_packed.py; 'packed_heads_xla' the gather of
    every row's whole table by name (`packed_decode_xla`), what the
    read was before the kernel. Every `live_every`-th row is live, of
    about 510 tokens when some are dead (the cell's rows), of 1 to
    2,560 when all are; the rest have length 0 and give zeros."""
    q_heads, kv_heads, head_dim = heads

    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.ops import paged_attention as paged_ops
        from skypilot_tpu.ops import pallas_paged
        kq, kk, kv, kp, kl = jax.random.split(key, 5)
        total = batch * pages_per_seq + 1
        shape = (1, total, PAGE, kv_heads * head_dim)
        k_pages = jax.random.normal(kk, shape, jnp.bfloat16)
        v_pages = jax.random.normal(kv, shape, jnp.bfloat16)
        pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        got = pallas_paged.resolve_impl(
            layout='kv_packed',
            decode_pool=pool if route == 'packed_decode' else None)
        if got != route:
            return _wrong_route(route, got)
        tbl = (jax.random.permutation(kp, total - 1) + 1).reshape(
            batch, pages_per_seq).astype(jnp.int32)
        q = jax.random.normal(kq, (batch, q_heads, head_dim), jnp.bfloat16)
        if live_every == 1:
            lengths = jax.random.randint(kl, (batch,), 1,
                                         PAGE * pages_per_seq + 1, jnp.int32)
        else:
            lengths = jnp.where(
                jnp.arange(batch) % live_every == 0,
                jax.random.randint(kl, (batch,), 384, 640, jnp.int32), 0)
        unpack = lambda p: jnp.moveaxis(p[0].reshape(  # noqa: E731
            total, PAGE, kv_heads, head_dim), 2, 0)
        one = jax.jit(paged_ops._reference_paged_attention)

        def reference(q, k_pages, v_pages, lengths, tbl):
            k, v = unpack(k_pages), unpack(v_pages)
            out = jnp.concatenate([
                one(q[lo:lo + 16], k, v, jnp.maximum(lengths[lo:lo + 16], 1),
                    tbl[lo:lo + 16]) for lo in range(0, batch, 16)])
            return jnp.where((lengths > 0)[:, None, None], out, 0)

        read = (paged_ops.packed_decode_attention if route == 'packed_decode'
                else paged_ops.packed_decode_xla)
        res = _compare(jax.jit(read), reference,
                       (q, k_pages, v_pages, lengths, tbl))
        res.update(context_tokens=int(lengths.sum()),
                   live_rows=int((lengths > 0).sum()))
        return res
    return case


def _streamed(k: int, n: int, rows: int = 128) -> Callable[[Any], Dict]:
    """A decode round's product through ops/pallas_streamed, the matrix
    read from HBM inside the call: `lfm2-24b-a2b-l10`'s conv
    projections at its 128 slots, held to the float32 product of the
    same bf16 values. Its `run_us` is the host's enqueue of a call
    (some 210 us), not the kernel's 13-44 us: a traced run has those."""
    def case(key):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.ops import pallas_streamed
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (rows, k), jnp.bfloat16)
        w = (0.02 * jax.random.normal(kw, (k, n), jnp.float32)
             ).astype(jnp.bfloat16)

        def reference(x, w):
            return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))

        return _compare(jax.jit(pallas_streamed.streamed_matmul), reference,
                        (x, w))
    return case


def _compare(kernel_fn, ref_fn, args, relative_to_max: bool = False
             ) -> Dict[str, Any]:
    """Compile + run both; the kernel's compile is where Mosaic
    refuses, so it is timed and lowered on its own."""
    import jax
    import numpy as np
    t0 = time.perf_counter()
    compiled = kernel_fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    got = jax.block_until_ready(compiled(*args))
    # Twenty calls enqueued back to back and waited for once, so that
    # the device's time shows and not the wait's round trip (some
    # 0.5 ms a call here); the median of five such batches.
    batches = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(RUNS):
            out = compiled(*args)
        jax.block_until_ready(out)
        batches.append((time.perf_counter() - t0) / RUNS)
    run_us = sorted(batches)[len(batches) // 2] * 1e6
    with jax.default_matmul_precision('highest'):
        want = jax.block_until_ready(ref_fn(*args))
    worst = 0.0
    ok = True
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        if g.shape != w.shape or not np.all(np.isfinite(g)):
            return {'verdict': 'mismatch', 'detail':
                    f'shape {g.shape} vs {w.shape} or non-finite values'}
        scale = float(np.max(np.abs(w))) if relative_to_max else 1.0
        err = np.abs(g - w)
        bound = ATOL * max(scale, 1.0) + RTOL * np.abs(w)
        worst = max(worst, float(np.max(err)) / max(scale, 1.0))
        ok = ok and bool(np.all(err <= bound))
    return {'verdict': 'ok' if ok else 'mismatch',
            'max_abs_err': round(worst, 6),
            'compile_s': round(compile_s, 2),
            'run_us': round(run_us, 1)}


def cases() -> List[tuple]:
    """(name, route users reach it by, case fn[, inputs]): cases that
    name the same `inputs` are given the same key, so the same
    arrays."""
    return [
        ('paged_decode_attention/bf16/S=1',
         '`resolve_impl` bf16 pool of 128-wide heads: decode',
         _paged_decode('decode', quantized=False)),
        ('paged_decode_attention/bf16/S=1/32rows',
         "the same at the benchmark cells' 32 slots, 8 of them dead",
         _paged_decode('decode', quantized=False, batch=32,
                       dead_every=4), '32rows'),
        ('paged_decode_attention/bf16/S=1/32rows/tensor_mesh',
         'the same under --tensor N: each chip on its kv-head slice',
         _paged_decode('decode', quantized=False, batch=32,
                       dead_every=4, over_tensor_mesh=True), '32rows'),
        ('xla_paged_attention/bf16/D=64/S=1',
         '`resolve_impl` bf16 pool of 64-wide heads: xla',
         _paged_decode('xla', quantized=False, heads=(12, 12, 64))),
        ('fused_paged_attention/int8/S=1',
         '`resolve_impl` int8 pool: decode',
         _paged_decode('fused', quantized=True)),
        ('fused_paged_attention/int8/S=256',
         '`resolve_impl` int8 pool: suffix prefill chunk',
         _paged_chunk(quantized=True, chunk=256, batch=2)),
        ('fused_paged_attention/bf16/S=1',
         '`fused_paged_attention` by name on a bf16 pool: decode',
         _paged_decode('fused', quantized=False)),
        ('fused_paged_attention/bf16/S=256',
         '`fused_paged_attention` by name on a bf16 pool: chunk',
         _paged_chunk(quantized=False, chunk=256, batch=2)),
        ('fused_qkv_lora_delta/S=1',
         'int8 pool + --adapter-dir: decode',
         _qkv_lora(chunk=1, batch=BATCH)),
        ('fused_qkv_lora_delta/S=256',
         'int8 pool + --adapter-dir: prefill chunk',
         _qkv_lora(chunk=256, batch=1)),
        ('sparse_latent/index_scores/bf16/S=1/48rows',
         '`resolve_impl` latent pool: the decode round\'s index read',
         _sparse_latent('index')),
        ('sparse_latent/select_attend/bf16/S=1/48rows',
         'the same: top-2048 selection and attention over the rows',
         _sparse_latent('attend')),
        ('sparse_latent/chunk/bf16/S=512',
         'the same: a prefill chunk 5,632 tokens into its context '
         '(12 blocks of keys), its attention the Pallas kernel',
         _sparse_latent('chunk')),
        ('sparse_latent/chunk/bf16/S=512/28blocks',
         'the same 13,824 tokens in: the slope a block of keys',
         _sparse_latent('chunk', offset=13824)),
        ('ssm/update/f32/S=1/128rows/43live',
         'a model with state by slot (models/nemotron_h.py): a decode '
         'round\'s step, a third of the 128 lanes live',
         _ssm('update', live_lanes=43), 'ssm_update'),
        ('ssm/update/f32/S=1/128rows/128live',
         'the same with every lane live: the slope a live row',
         _ssm('update', live_lanes=128), 'ssm_update'),
        ('ssm/scan/f32/S=512',
         'the same: a 512-token prefill chunk from a given state, 37 '
         'positions of padding',
         _ssm('scan')),
        ('experts/relu2/decode',
         'a routed model (models/deepseek.MoEByShare) on a TPU: the '
         'held experts of nemotron3-super-l11-ep4, a decode round of '
         '128 slots, a third live (2,816 rows)',
         _experts('relu2', 'decode')),
        ('experts/relu2/chunk',
         'the same: a 512-token prefill chunk (11,264 rows)',
         _experts('relu2', 'chunk')),
        ('experts/swiglu/decode',
         'the held experts of deepseek-v32-l5-ep16: a decode round of '
         '48 slots, a third live (384 rows)',
         _experts('swiglu', 'decode')),
        ('experts/swiglu/chunk',
         'the same: a 512-token prefill chunk (4,096 rows)',
         _experts('swiglu', 'chunk')),
        ('experts/swiglu/lfm2/decode',
         'the held experts of lfm2-24b-a2b-l10, all 64 of 2,048 x '
         '1,536: a decode round of 128 slots, a third live (172 rows)',
         _experts('lfm2', 'decode')),
        ('experts/swiglu/lfm2/chunk',
         'the same: a 256-token prefill chunk (1,024 rows)',
         _experts('lfm2', 'chunk')),
        ('packed_paged_attention/bf16/D=64/S=1/128rows',
         'a pool of 64-wide heads packed a token a row (models/lfm2.py) '
         'read by the gather of every row\'s whole table by name, '
         'packed_heads_xla, at lfm2-24b-a2b-l10\'s 128 slots of 2,560 '
         'tokens',
         _packed_decode('packed_heads_xla')),
        ('packed_paged_attention/kernel/bf16/D=64/S=1/128rows/32live',
         '`resolve_impl` the same pool\'s decode read on a TPU: '
         'packed_decode, the kernel of ops/pallas_packed.py; the cell\'s '
         'round, 32 rows of about 510 tokens live and 96 of length 0',
         _packed_decode('packed_decode', live_every=4)),
        ('packed_paged_attention/kernel/bf16/D=64/S=1/128rows/128live',
         'the same with every row live, of 1 to 2,560 tokens',
         _packed_decode('packed_decode')),
        ('streamed_matmul/bf16/lfm2_in_proj/128rows',
         "models/lfm2.py's conv in_proj in a decode round on a TPU: "
         '[128, 2,048] x [2,048, 6,144]',
         _streamed(2048, 6144)),
        ('streamed_matmul/bf16/lfm2_out_proj/128rows',
         'the same, out_proj: [128, 2,048] x [2,048, 2,048]',
         _streamed(2048, 2048)),
        ('upstream_flash_attention/fwd+bwd/D=64/S=2048',
         'train_lm --seq >= 2048, GPT-2 heads',
         _flash(batch=2, heads=12, kv_heads=12, head_dim=64)),
        ('upstream_flash_attention/fwd+bwd/D=128/S=2048',
         'train_lm --seq >= 2048, Llama-3 heads (GQA 32/8)',
         _flash(batch=1, heads=Q_HEADS, kv_heads=KV_HEADS,
                head_dim=HEAD_DIM)),
    ]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--out', default=None, metavar='PATH',
                        help='also write the verdicts as JSON')
    parser.add_argument('--only', default='', metavar='TEXT',
                        help='run the cases whose name contains TEXT '
                             '(a four-chip host: tensor_mesh)')
    args = parser.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    print(f'kernel_check: device {json.dumps(device)}', flush=True)
    if dev.platform != 'tpu':
        print(f'kernel_check: FAILED — found platform {dev.platform!r}; '
              f'Mosaic compiles for a TPU only and interpret mode '
              f'proves nothing about it', flush=True)
        return 1

    results = []
    key = jax.random.PRNGKey(args.seed)
    for i, (name, reached_by, case, *inputs) in enumerate(cases()):
        if args.only not in name:
            continue
        if inputs:
            i = zlib.crc32(inputs[0].encode()) % (1 << 31)
        try:
            res = case(jax.random.fold_in(key, i))
        except Exception as e:  # pylint: disable=broad-except
            # The boundary this tool exists for: a Mosaic refusal is a
            # result to report, in the compiler's words, not a crash.
            traceback.print_exc()
            res = {'verdict': 'refused',
                   'error': f'{type(e).__name__}: {e}'[:4000]}
        res.update(name=name, reached_by=reached_by)
        results.append(res)
        print(f'kernel_check: {json.dumps(res)}', flush=True)
    if args.out:
        with open(args.out, 'w', encoding='utf-8') as f:
            json.dump({'device': device, 'atol': ATOL, 'rtol': RTOL,
                       'results': results}, f, indent=1)
    bad = [r['name'] for r in results if r['verdict'] != 'ok']
    print(f'kernel_check: {len(results) - len(bad)}/{len(results)} ok'
          + (f'; NOT ok: {bad}' if bad else ''), flush=True)
    return 1 if bad else 0


if __name__ == '__main__':
    sys.exit(main())
