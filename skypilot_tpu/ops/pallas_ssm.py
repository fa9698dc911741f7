"""A decode round's Mamba-2 state update in one Pallas TPU pass a layer:
each live slot's state read once and written once where it lies, the
next block's read in flight while this one computes and the last one's
write drains.

`ops/ssm.ssm_update`'s loop takes one live row a step with a dozen small
operations round two passes over its 4 MB of state (the update in place,
then a second read for y = h C): 29.8 us a live row of a layer on a
v5e where the row's bytes ask 10.4 (PERF.md). `state_update_pallas` is
the same arithmetic in one pass:

  - The state [slots, P, N, H] and the tail [slots, (K - 1) C / H, H]
    stay in HBM (`pl.ANY`), aliased to their outputs. A loop inside the
    kernel walks the live rows in `order` (live lanes first; their
    number is scalar-prefetched): a row's state in blocks of `_block_p`
    values of P, each copied into one of three VMEM buffers, updated
    there and copied back. Block b + 1's read is started before block
    b is waited for, so a read and a write are in flight while a block
    computes. A dead lane's rows are neither read nor written.
  - y = h C + D x is taken from the block while it sits in VMEM, into
    the output y, which is held in VMEM whole (a dead lane's row zeros)
    and written out once.
  - The slot's layout puts the heads on the lanes (ops/ssm.slot_shapes):
    exp(dt A) and dt x are lane vectors, B and C of a head's group are
    spread over the lanes once a row (a product with a 0/1 matrix at
    `highest` precision, exact), and y is a sum over sublanes. No
    reduction runs along the lanes.
  - A row's first block also takes its convolution step (`ssm.
    conv_step`: float32 sums, silu, the activation in the input's
    dtype) from its tail and its token, and writes the shifted tail
    back to its slot.

State, A, dt and every product of the recurrence are float32, as in
the loop. The call is jitted on its own (`_update_call`), so a program
of five Mamba layers lowers the kernel once per operand shape.

`resolve_route` chooses between this kernel ('state_pallas') and the
loop ('live_rows_loop': the CPU backend, a refused shape, a mesh of
several devices, and `kernel_check`'s reference) from the backend and
the static shapes.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.ops.ssm import EXACT, F32, conv_step

#: Bytes of state a block (and each of the three VMEM buffers) holds:
#: 16 values of P at the published widths (N = H = 128). On a v5e the
#: copies set the pace: 1 and 2 MiB blocks, three or four buffers, and
#: the copies alone with no arithmetic all run a live row in the same
#: 13 us (PERF.md).
_BLOCK_BYTES = 1 << 20
#: VMEM for y, held whole (two buffers of slots x P x H float32).
_Y_BUDGET = 32 << 20
_SUBLANES = {4: 8, 2: 16}


def _block_p(hd: int, n: int, heads: int) -> Optional[int]:
    """The most values of P a block takes: a multiple of 8 that
    divides P, within `_BLOCK_BYTES`; None when not even 8 does."""
    best = None
    for p in range(8, hd + 1, 8):
        if hd % p == 0 and p * n * heads * 4 <= _BLOCK_BYTES:
            best = p
    return best


def kernel_refusal(heads: int, hd: int, n: int, groups: int, width: int,
                   taps: int, dtype: Any, slots: int) -> Optional[str]:
    """Why Mosaic does not take a state update of these static shapes
    (H heads of P values, state N, G groups, C = `width` channels of
    the convolution of K = `taps`, the tail in `dtype`, `slots` rows),
    or None when it does: whole lane rows of H values, B and C of a
    group one lane row each (N = H), whole sublane tiles of the tail's
    channel rows, a block of P that fits `_BLOCK_BYTES` and a y that
    fits `_Y_BUDGET`."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize not in _SUBLANES or not jnp.issubdtype(
            dtype, jnp.floating):
        return f'a tail of {dtype.name} is not bf16/f16/f32'
    if heads % 128:
        return f'{heads} heads are not whole rows of 128 lanes'
    if n != heads:
        return (f'a state of {n} is not one lane row of {heads} heads '
                f'(B and C a group spread over the heads)')
    if heads % groups:
        return f'{groups} groups do not divide {heads} heads'
    if heads % hd:
        return f'heads of {hd} do not fill rows of {heads} channels'
    if width != heads * hd + 2 * groups * n:
        return f'{width} channels are not x, B and C'
    if (width // heads) % _SUBLANES[dtype.itemsize]:
        return (f'{width // heads} channel rows are not whole sublane '
                f'tiles of {dtype.name}')
    if taps < 2:
        return 'a convolution of one tap keeps no tail'
    if _block_p(hd, n, heads) is None:
        return (f'8 values of P ({8 * n * heads * 4} B) exceed the '
                f'{_BLOCK_BYTES >> 20} MiB block')
    if 2 * slots * hd * heads * 4 > _Y_BUDGET:
        return (f'y of {slots} slots exceeds the {_Y_BUDGET >> 20} MiB '
                f'it may hold in VMEM')
    return None


def available() -> bool:
    """True where Mosaic compiles the kernel: a TPU backend."""
    return jax.default_backend() == 'tpu'


def resolve_route(heads: int, hd: int, n: int, groups: int, width: int,
                  taps: int, dtype: Any, slots: int) -> str:
    """The route of a decode round's state update, from what the code
    can observe: 'state_pallas' on a TPU for shapes `kernel_refusal`
    takes, outside a mesh of more than one device (the call is opaque
    to GSPMD); 'live_rows_loop' otherwise."""
    from skypilot_tpu.ops.attention import _active_mesh
    mesh = _active_mesh()
    if (not available() or (mesh is not None and mesh.size > 1)
            or kernel_refusal(heads, hd, n, groups, width, taps, dtype,
                              slots) is not None):
        return 'live_rows_loop'
    return 'state_pallas'


def _kernel(taps, groups, act_dtype, order, rows_ref, dt_ref, a_ref, d_ref,
            w_ref, bias_ref, state_in, tail_in, xbc_in, y_out, state_out,
            tail_out, sbuf, tbuf, xbuf, dec, dtx, dx, bexp, cexp, ssem,
            tsem):
    """One invocation: the live rows' loop.

    Refs: `order` i32[slots] and the live count in SMEM; dt [slots, H],
    a and d [1, H], the convolution's weight [K, C / H, H] and bias
    [C / H, H] (float32) in VMEM; the state [slots, P, N, H], the tail
    [slots, (K - 1) C / H, H] and the tokens' inputs [slots, C / H, H]
    where they lie; y [slots, P, H] in VMEM; the state and the tail out
    (aliased to theirs in). Scratch: the three state buffers [3, block,
    N, H]; a row's tail and input, two each; the row's exp(dt A) [1, H],
    dt x and D x [P, H], B and C over the heads [N, H]; DMA semaphores
    (state [read / write, buffer], tail [read tail / read input /
    write, buffer])."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    slots, hd, n, heads = state_in.shape
    block = sbuf.shape[1]
    nb = hd // block
    cr = xbc_in.shape[1]
    rows = rows_ref[0]
    steps = rows * nb

    def state_copy(b, write):
        """Block b of the walk (row b // nb of `order`, block b % nb),
        in buffer b % 3."""
        blk = pl.ds(pl.multiple_of((b % nb) * block, block), block)
        s = b % 3
        r = order[b // nb]
        if write:
            return pltpu.make_async_copy(sbuf.at[s], state_out.at[r, blk],
                                         ssem.at[1, s])
        return pltpu.make_async_copy(state_in.at[r, blk], sbuf.at[s],
                                     ssem.at[0, s])

    def row_reads(i):
        t = i % 2
        return (pltpu.make_async_copy(tail_in.at[order[i]], tbuf.at[t],
                                      tsem.at[0, t]),
                pltpu.make_async_copy(xbc_in.at[order[i]], xbuf.at[t],
                                      tsem.at[1, t]))

    def tail_write(i):
        return pltpu.make_async_copy(tbuf.at[i % 2], tail_out.at[order[i]],
                                     tsem.at[2, i % 2])

    @pl.when(rows > 0)
    def _():
        for copy in row_reads(0):
            copy.start()
        state_copy(0, False).start()

    def zero_row(r, carry):
        y_out[r] = jnp.zeros((hd, heads), F32)
        return carry

    jax.lax.fori_loop(0, slots, zero_row, 0)

    sub = jax.lax.broadcasted_iota(jnp.int32, (8, heads), 0)
    head = jax.lax.broadcasted_iota(jnp.int32, (groups, heads), 1)
    group = jax.lax.broadcasted_iota(jnp.int32, (groups, heads), 0)
    per = heads // groups
    # expand[g, h] = 1 where head h is in group g.
    expand = jnp.logical_and(head >= group * per,
                             head < (group + 1) * per).astype(F32)
    # The x channels' rows of H: `per_row` heads of P a row.
    per_row = heads // hd if heads % hd == 0 else 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (hd, heads), 1)
    # pick[k, h] = 1 where head h lies in row k.
    pick = (jax.lax.broadcasted_iota(jnp.int32, (hd, heads), 1)
            // max(per_row, 1)
            == jax.lax.broadcasted_iota(jnp.int32, (hd, heads), 0)
            ).astype(F32)
    spread = (((0,), (0,)), ((), ()))

    def start_row(i):
        """Row i's convolution step and what its blocks share."""
        t = i % 2
        for copy in row_reads(i):
            copy.wait()

        @pl.when(i >= 1)
        def _():
            tail_write(i - 1).wait()       # frees the other tail buffer

        @pl.when(i + 1 < rows)
        def _():
            for copy in row_reads(i + 1):
                copy.start()

        previous = [tbuf[t, k * cr:(k + 1) * cr] for k in range(taps - 1)]
        token = xbuf[t]
        act = conv_step(previous, token, [w_ref[k] for k in range(taps)],
                        bias_ref[...], act_dtype).astype(F32)
        for k in range(taps - 2):
            tbuf[t, k * cr:(k + 1) * cr] = previous[k + 1]
        tbuf[t, (taps - 2) * cr:] = token
        tail_write(i).start()
        # x in the model's order is [H, P]: rows of `per_row` heads.
        # x_t[p, h] = z[(h % per_row) P + p, h], z = the rows spread
        # over the heads (a 0/1 product: exact).
        if per_row:
            z = jax.lax.dot_general(act[:hd], pick, spread,
                                    precision=EXACT,
                                    preferred_element_type=F32)
            x = z[:hd]
            for m in range(1, per_row):
                x = jnp.where(lane % per_row == m, z[m * hd:(m + 1) * hd],
                              x)
        else:               # a head over several rows: interpret mode only
            x = act[:hd].reshape(heads, hd).T
        bc_rows = groups * n // heads
        b = act[hd:hd + bc_rows].reshape(groups, n)
        c = act[hd + bc_rows:].reshape(groups, n)
        bexp[...] = jax.lax.dot_general(b, expand, spread, precision=EXACT,
                                        preferred_element_type=F32)
        cexp[...] = jax.lax.dot_general(c, expand, spread, precision=EXACT,
                                        preferred_element_type=F32)
        dt = dt_ref[pl.ds(order[i], 1), :]                    # [1, H]
        dec[...] = jnp.exp(dt * a_ref[...])
        dtx[...] = dt * x
        dx[...] = d_ref[...] * x

    def compute(s, j, i):
        """Block j of row i, in buffer s: the state advanced in place
        and its values of y, eight values of P at a time."""
        yr = y_out.at[order[i]]

        def eight(u, carry):
            p = pl.multiple_of(j * block + u * 8, 8)
            q = u * 8
            dtx8 = dtx[pl.ds(p, 8), :]
            y8 = jnp.zeros((8, heads), F32)
            for k in range(8):
                h = (sbuf[s, q + k] * dec[...]
                     + bexp[...] * dtx8[k:k + 1, :])
                sbuf[s, q + k] = h
                yk = jnp.sum(h * cexp[...], axis=0, keepdims=True)
                y8 = jnp.where(sub == k, yk, y8)
            yr[pl.ds(p, 8), :] = y8 + dx[pl.ds(p, 8), :]
            return carry

        jax.lax.fori_loop(0, block // 8, eight, 0)

    def step(b, carry):
        i, j, s = b // nb, b % nb, b % 3

        @pl.when(b >= 2)
        def _():
            state_copy(b - 2, True).wait()     # frees buffer (b + 1) % 3

        @pl.when(b + 1 < steps)
        def _():
            state_copy(b + 1, False).start()

        @pl.when(j == 0)
        def _():
            start_row(i)

        state_copy(b, False).wait()
        compute(s, j, i)
        state_copy(b, True).start()
        return carry

    jax.lax.fori_loop(0, steps, step, 0)

    # Drain what is still in flight.
    @pl.when(steps >= 2)
    def _():
        state_copy(steps - 2, True).wait()

    @pl.when(rows >= 1)
    def _():
        state_copy(steps - 1, True).wait()
        tail_write(rows - 1).wait()


@functools.partial(jax.jit, static_argnames=('groups', 'act_dtype',
                                             'interpret'))
def _update_call(state, tail, xbc, dt, a, d, weight, bias, order, rows, *,
                 groups, act_dtype, interpret):
    """Jitted on its own, as `pallas_experts._grouped_call` is: a
    program of five Mamba layers traces and lowers the kernel once per
    operand shape."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    slots, hd, n, heads = state.shape
    taps, cr, _ = weight.shape
    block = _block_p(hd, n, heads)
    y_shape = jax.ShapeDtypeStruct((slots, hd, heads), F32)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda g, *_: (0,) * len(x.shape))

    vmem_in = [dt, a.reshape(1, heads), d.reshape(1, heads), weight, bias]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [
        pltpu.VMEM((3, block, n, heads), F32),
        pltpu.VMEM((2,) + tail.shape[1:], tail.dtype),
        pltpu.VMEM((2, cr, heads), xbc.dtype),
        pltpu.VMEM((1, heads), F32),
        pltpu.VMEM((hd, heads), F32),
        pltpu.VMEM((hd, heads), F32),
        pltpu.VMEM((n, heads), F32),
        pltpu.VMEM((n, heads), F32),
        pltpu.SemaphoreType.DMA((2, 3)),
        pltpu.SemaphoreType.DMA((3, 2)),
    ]
    # The buffers, y and the inputs held in VMEM (two of each), and room
    # for the compiler's own.
    vmem = (3 * block * n * heads * 4 + 2 * slots * hd * heads * 4
            + 4 * tail[0].size * 4 + 2 * sum(x.size * 4 for x in vmem_in)
            + (8 << 20))
    n_prefetch = 2
    y, state, tail = pl.pallas_call(
        functools.partial(_kernel, taps, groups, act_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch, grid=(1,),
            in_specs=[whole(x) for x in vmem_in] + [anywhere] * 3,
            out_specs=[whole(y_shape), anywhere, anywhere],
            scratch_shapes=scratch),
        out_shape=(y_shape,
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)),
        # The state and the tail (operands 7 and 8, after the prefetched
        # two and the five in VMEM) are updated where they lie.
        input_output_aliases={n_prefetch + 5: 1, n_prefetch + 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=int(vmem)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='ssm_state_update',
    )(order, rows.reshape(1), *vmem_in, state, tail, xbc)
    return y, state, tail


def state_update_pallas(state: jax.Array, tail: jax.Array, xbc: jax.Array,
                        dt: jax.Array, a: jax.Array, d: jax.Array,
                        weight: jax.Array, bias: jax.Array,
                        order: jax.Array, rows: jax.Array, *, groups: int,
                        act_dtype: Any, interpret: bool = False):
    """The live rows' step (route 'state_pallas') in the slot's layout
    (ops/ssm.py: `ssm_update` gives it so).

    state f32[slots, P, N, H]; tail [slots, (K - 1) C / H, H]; xbc
    [slots, C / H, H] the tokens' inputs in the tail's dtype; dt
    f32[slots, H]; a, d f32[H]; weight f32[K, C / H, H], bias
    f32[C / H, H]; order i32[slots] (the live lanes first), rows i32
    (their number); the activation is cast to `act_dtype`. Returns (y
    f32[slots, P, H], zeros on a dead lane; the state and the tail,
    updated in place)."""
    slots, hd, n, heads = state.shape
    taps = weight.shape[0]
    width = weight.shape[1] * heads
    refusal = kernel_refusal(heads, hd, n, groups, width, taps, tail.dtype,
                             slots)
    if refusal is not None and not interpret:
        raise ValueError(f"route 'state_pallas': {refusal}")
    return _update_call(state, tail, xbc, dt.astype(F32), a.astype(F32),
                        d.astype(F32), weight, bias, order.astype(jnp.int32),
                        jnp.asarray(rows, jnp.int32), groups=groups,
                        act_dtype=jnp.dtype(act_dtype), interpret=interpret)
