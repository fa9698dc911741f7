"""A decode round's product `x @ w` in one Pallas TPU pass that reads
its matrix from HBM itself.

Why. Left to XLA:TPU, a product whose matrix is a program parameter
has the matrix copied into VMEM by memory-space assignment, in slices,
during the operations scheduled before it: LFM2's 25 MB `in_proj` of a
conv layer arrives in four slices while the layer below combines its
experts and the operator norm runs, and the waits for them are
operations of no scope. The read then belongs to no named scope, and
a scope's roofline over the product counts bytes whose time lies
elsewhere (a v5e trace read LFM2's `short_conv` at 110% of its
roofline so). Here the matrix is pinned in HBM
(`pltpu.with_memory_space_constraint`: the call's operand memory space,
which assignment keeps; an `HBM` block spec alone it does not) and read
in row tiles into two VMEM slots by hand, the next tile's copy in
flight while the current one multiplies: the read lies inside the call,
and so inside whatever scope the caller names.

The arithmetic is XLA's: bf16 operands, products accumulated in
float32 over the tiles in order, the result rounded once to `x`'s
dtype.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: Bytes of the matrix one tile holds: two slots of it in VMEM beside
#: the rows, the float32 sum and the result.
_TILE_BYTES = 2 << 20
#: The call's VMEM: LFM2's `in_proj` ([128, 2,048] x [2,048, 6,144])
#: takes 2 x 1.5 MB of tiles, 3 MB of sum, 0.5 + 1.5 MB of rows and
#: result.
_VMEM_LIMIT = 32 << 20
_SUBLANES = {4: 8, 2: 16}


def _tile_rows(k: int, n: int, itemsize: int) -> Optional[int]:
    """The most rows of the matrix, a multiple of 128 that divides `k`,
    whose tile fits `_TILE_BYTES`; None if not even 128 do."""
    best = None
    for rows in range(128, k + 1, 128):
        if k % rows == 0 and rows * n * itemsize <= _TILE_BYTES:
            best = rows
    return best


def refusal(m: int, k: int, n: int, dtype: Any) -> Optional[str]:
    """Why the kernel does not take `[m, k] @ [k, n]` of `dtype`, or
    None where it does."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize not in _SUBLANES:
        return f'{dtype} is not a 16- or 32-bit type'
    if m % _SUBLANES[dtype.itemsize]:
        return (f'{m} rows are not a multiple of '
                f'{_SUBLANES[dtype.itemsize]} sublanes')
    if k % 128 or n % 128:
        return f'[{k}, {n}] is not a multiple of 128 lanes each way'
    if _tile_rows(k, n, dtype.itemsize) is None:
        return (f'128 rows of a {n}-wide matrix exceed the '
                f'{_TILE_BYTES >> 20} MiB tile')
    if m * (k + n) * dtype.itemsize + m * n * 4 > _VMEM_LIMIT // 2:
        return f'{m} rows and their sums exceed half the VMEM budget'
    return None


def available() -> bool:
    """True where Mosaic compiles the kernel: a TPU backend."""
    return jax.default_backend() == 'tpu'


def _kernel(tile, tiles, x_ref, w_hbm, o_ref, slots, sems, acc):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def copy(t):
        return pltpu.make_async_copy(w_hbm.at[pl.ds(t * tile, tile)],
                                     slots.at[t % 2], sems.at[t % 2])

    copy(0).start()
    for t in range(tiles):
        if t + 1 < tiles:
            copy(t + 1).start()
        copy(t).wait()
        part = jnp.dot(x_ref[:, t * tile:(t + 1) * tile], slots[t % 2],
                       preferred_element_type=F32)
        if t == 0:
            acc[...] = part
        else:
            acc[...] += part
    o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('interpret',))
def _streamed_call(x, w, *, interpret):
    """Jitted on its own, as `pallas_experts._grouped_call` is: a
    program lowers the kernel once per operand shape."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m, k = x.shape
    n = w.shape[1]
    tile = _tile_rows(k, n, w.dtype.itemsize)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, tile, k // tile),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        in_specs=[vmem, pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((2, tile, n), w.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((m, n), F32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name='streamed_matmul',
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x, w if interpret else pltpu.with_memory_space_constraint(
        w, pltpu.HBM))


def streamed_matmul(x: jax.Array, w: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """x [m, k] @ w [k, n] -> [m, n] of x's dtype, `w` read from HBM
    inside the call (module docstring). The shapes must pass
    `refusal`."""
    why = refusal(x.shape[0], x.shape[1], w.shape[1], w.dtype)
    if why is not None:
        raise ValueError(f'streamed_matmul: {why}')
    return _streamed_call(x, w.astype(x.dtype), interpret=interpret)
