"""The held experts of a routed layer in one Pallas TPU pass: each
touched expert's own matrices streamed from HBM, the next one's copy in
flight while the current one multiplies.

`models/deepseek.grouped_experts` walks blocks of expert-sorted rows in
a `fori_loop` whose step is a `lax.switch` over the held experts'
arrays: one product at a time, nothing overlapping an expert's read
with the previous expert's product (a touched expert of
`nemotron3-super-l11-ep4` cost 35 us where its two 5.5 MB matrices are
13.4 us of bytes on a v5e: PERF.md), and a program carries the 128
branches of every expert layer. `grouped_experts_pallas` is the same
arithmetic: bf16 products accumulated in float32, the expert's
activation between them (`expert_hidden`), each row written by the
expert it was sorted to and every row past `sum(counts)` zero.

How it runs. The rows are cut into aligned blocks of `_row_block`
rows; a grid step is one (block, expert) visit, in the order of the
sorted rows, so a block that two experts share is visited once by each
and each writes its own rows (the first visit of a block zeroes it).
A scalar-prefetched plan (`_plan`, plain XLA from `counts`) gives each
step its expert, its block and that expert's rows in it; steps past
the last visit zero the blocks no expert reached and then do nothing.
The matrices stay where they lie, one array each, and are copied into
two VMEM slots by hand: a step that meets a new expert waits for its
copy and starts the copy of the next new expert, so an expert with no
rows is never read and one that spans two blocks is read once. A
matrix pair that does not fit `_WEIGHT_BUDGET` is cut along the
expert's width into tiles (`_width_tile`) that are streamed in turn.
Only the start of a copy depends on which expert it is (a tree of
`lax.cond` over the held arrays, `_dispatch`); the products, the
activation and the masked write appear once in the body, and the call
is jitted on its own (`_grouped_call`), so a program of L expert
layers traces and lowers the kernel once per operand shape.

`resolve_route` chooses between this kernel ('grouped_pallas') and the
loop ('switch_loop': the CPU backend, a refused shape, a mesh of more
than one device, and `kernel_check`'s reference) from the backend and
the static shapes alone.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: Matrices an expert takes by activation, in the order they are
#: passed: the inner ones ([dim, width]) first, `w_down` ([width, dim])
#: last.
ACTS = {'swiglu': 3, 'relu2': 2}

#: VMEM the two slots of an expert's matrices (one width tile of each)
#: may take. Nemotron 3 Super's latent experts (1,024 x 2,688, two
#: matrices) fit whole in 22 MB; DeepSeek-V3.2's (7,168 x 2,048, three)
#: stream in tiles of 512 of their width, 44 MB.
_WEIGHT_BUDGET = 48 << 20
#: Rows a grid step multiplies. A product of fewer than 128 rows by a
#: 128 x 128 tile of the matrices costs a v5e's matrix unit as much as
#: one of 128 (the tile's load, not the rows, sets it), so a decode
#: round's few rows an expert gain nothing from a smaller block, and a
#: larger one is split by fewer experts.
_ROW_BLOCK = 128
_SUBLANES = {4: 8, 2: 16}

# Rows of the plan, each `steps` long (`_plan`).
(_EXPERT, _X_BLOCK, _O_BLOCK, _LO, _HI, _REAL, _FIRST, _FRESH, _SLOT,
 _NEXT) = range(10)


def expert_hidden(act: str, x: jax.Array, *w_in: jax.Array) -> jax.Array:
    """An expert's hidden values, float32 [rows, width]: 'swiglu'
    silu(x w_gate) * (x w_up), 'relu2' relu(x w_up)^2; operands as
    given, products accumulated in float32."""
    if act == 'swiglu':
        w_gate, w_up = w_in
        gate = jnp.dot(x, w_gate, preferred_element_type=F32)
        up = jnp.dot(x, w_up, preferred_element_type=F32)
        return jax.nn.silu(gate) * up
    if act == 'relu2':
        (w_up,) = w_in
        return jnp.square(jax.nn.relu(
            jnp.dot(x, w_up, preferred_element_type=F32)))
    raise ValueError(f'unknown expert activation {act!r} (choices: '
                     f'{", ".join(ACTS)})')


def _width_tile(dim: int, width: int, n_mat: int, itemsize: int
                ) -> Optional[int]:
    """The widest tile of an expert's width (a multiple of 128 lanes
    that divides it) whose two slots of all `n_mat` matrices fit
    `_WEIGHT_BUDGET`; None when not even 128 does."""
    best = None
    for tile in range(128, width + 1, 128):
        if (width % tile == 0
                and 2 * n_mat * dim * tile * itemsize <= _WEIGHT_BUDGET):
            best = tile
    return best


def kernel_refusal(dim: int, width: int, n_mat: int, dtype: Any
                   ) -> Optional[str]:
    """Why `grouped_experts_pallas` does not take experts of these
    static shapes (`n_mat` matrices [dim, width] ... [width, dim] of
    `dtype`, rows of `dim` values of the same dtype), or None when it
    does. A row block and a width tile must be whole (sublane,
    128-lane) tiles, and two width tiles of the matrices must fit
    `_WEIGHT_BUDGET`."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize not in _SUBLANES or not jnp.issubdtype(
            dtype, jnp.floating):
        return f'dtype {dtype.name} is not bf16/f16/f32'
    if n_mat not in ACTS.values():
        return f'{n_mat} matrices an expert (an activation takes 2 or 3)'
    if dim % 128 != 0:
        return f'rows of {dim} values are not a multiple of 128 lanes'
    if width % 128 != 0:
        return f'an expert {width} wide is not a multiple of 128 lanes'
    if _width_tile(dim, width, n_mat, dtype.itemsize) is None:
        return (f'two 128-wide tiles of {n_mat} matrices of {dim} rows '
                f'exceed the {_WEIGHT_BUDGET >> 20} MiB budget')
    return None


def available() -> bool:
    """True where Mosaic compiles the kernel: a TPU backend."""
    return jax.default_backend() == 'tpu'


def resolve_route(dim: int, width: int, n_mat: int, dtype: Any) -> str:
    """The route of one grouped expert pass, from what the code can
    observe: 'grouped_pallas' on a TPU for shapes `kernel_refusal`
    takes, outside a mesh of more than one device (the call is opaque
    to GSPMD, which would gather every sharded expert onto each chip);
    'switch_loop' otherwise."""
    from skypilot_tpu.ops.attention import _active_mesh
    mesh = _active_mesh()
    if (not available() or (mesh is not None and mesh.size > 1)
            or kernel_refusal(dim, width, n_mat, dtype) is not None):
        return 'switch_loop'
    return 'grouped_pallas'


def _row_block(rows: int, itemsize: int) -> int:
    """Rows a grid step takes: `_ROW_BLOCK`, or all of a smaller
    call's rows rounded up to a sublane tile."""
    sub = _SUBLANES[itemsize]
    return min(_ROW_BLOCK, -(-rows // sub) * sub)


def _plan(counts: jax.Array, block: int, blocks: int, steps: int,
          width_tiles: int) -> jax.Array:
    """The grid's plan, int32 [10 * steps] (row k of step s at k *
    steps + s), from `counts` (rows a held expert received, in sorted
    order):
      _EXPERT, _X_BLOCK, _O_BLOCK: the step's expert, the block of
        rows it reads (a step past the visits keeps the last one read,
        so no copy is made) and the block it writes;
      _LO, _HI: that expert's rows within the block, [lo, hi);
      _REAL: a visit (1) or a step past them (0); _FIRST: the first
        step that writes its block (it zeroes the block first);
      _FRESH: the visit waits for its expert's copy and starts the next
        fresh visit's (a new expert; every visit when the width is
        tiled); _SLOT: the VMEM slot of the visit's first width tile;
      _NEXT: the expert of the next fresh visit, or -1."""
    held = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first_block, last_block = starts // block, (ends - 1) // block
    visits = jnp.where(counts > 0, last_block - first_block + 1, 0)
    visits_end = jnp.cumsum(visits)
    n_real = visits_end[-1]
    s = jnp.arange(steps)
    e = jnp.minimum(jnp.sum(s[:, None] >= visits_end[None, :], axis=1),
                    held - 1)
    real = s < n_real
    # Blocks no expert reaches are zeroed by the steps past the visits,
    # one each, then the last block is named again (nothing to do).
    reached = (ends[-1] + block - 1) // block
    beyond = reached + s - n_real
    out_block = jnp.where(real, first_block[e] + s - (visits_end - visits)[e],
                          jnp.minimum(beyond, blocks - 1))
    before = jnp.concatenate([jnp.full((1,), -1), out_block[:-1]])
    first = jnp.where(real, out_block != before, beyond < blocks)
    last_read = out_block[jnp.maximum(n_real - 1, 0)]
    x_block = jnp.where(real, out_block,
                        jnp.where(n_real > 0, last_read, 0))
    lo = jnp.where(real, jnp.clip(starts[e] - out_block * block, 0, block),
                   0)
    hi = jnp.where(real, jnp.clip(ends[e] - out_block * block, 0, block), 0)
    prev_e = jnp.concatenate([jnp.full((1,), -1), e[:-1]])
    fresh = real & ((width_tiles > 1) | (e != prev_e))
    slot = ((jnp.cumsum(fresh) - 1) * width_tiles) % 2
    # The first fresh step after s: a reversed running minimum.
    at = jnp.where(fresh, s, steps)
    upto = jax.lax.cummin(at[::-1])[::-1]
    following = jnp.concatenate([upto[1:], jnp.full((1,), steps)])
    nxt = jnp.where(following < steps,
                    e[jnp.minimum(following, steps - 1)], -1)
    return jnp.stack([e, x_block, out_block, lo, hi, real, first, fresh,
                      jnp.where(real, slot, 0), nxt]
                     ).astype(jnp.int32).reshape(-1)


def _dispatch(e, lo: int, hi: int, leaf) -> None:
    """leaf(k) for k == e in [lo, hi): a tree of `lax.cond`, so the
    held arrays are told apart in log2(held) scalar branches."""
    if hi - lo == 1:
        leaf(lo)
        return
    mid = (lo + hi) // 2
    jax.lax.cond(e < mid, lambda: _dispatch(e, lo, mid, leaf),
                 lambda: _dispatch(e, mid, hi, leaf))


def _kernel(act, held, n_mat, width_tiles, steps, plan, x_ref, *refs):
    """Grid (steps,): one (block of rows, expert) visit a step.

    Refs: the plan in SMEM; x [block, dim] (the pipeline's block of the
    sorted rows); `held` x `n_mat` matrices where they lie (HBM), an
    expert's `n_mat` in a row; o [block, dim]; the slots of each
    matrix, [2, dim, tile] (inner) and [2, tile, dim] (`w_down`); acc
    f32 [block, dim]; DMA semaphores [slot, matrix]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    mats = refs[:held * n_mat]
    o_ref, *bufs = refs[held * n_mat:]
    slots, (acc, sems) = bufs[:n_mat], bufs[n_mat:]
    tile = slots[-1].shape[1]
    s = pl.program_id(0)

    def read(row):
        return plan[row * steps + s]

    def copies(expert, t, slot):
        """The copy descriptors of width tile t of one expert's
        matrices (`expert`: its refs) into `slot`."""
        out = []
        for j, w in enumerate(expert):
            if width_tiles > 1:
                cols = pl.ds(pl.multiple_of(t * tile, 128), tile)
                w = w.at[:, cols] if j < n_mat - 1 else w.at[cols, :]
            out.append(pltpu.make_async_copy(w, slots[j].at[slot],
                                             sems.at[slot, j]))
        return out

    def start(e, t, slot):
        def leaf(k):
            for copy in copies(mats[k * n_mat:(k + 1) * n_mat], t, slot):
                copy.start()
        _dispatch(e, 0, held, leaf)

    def wait(t, slot):
        # A wait counts the bytes of its destination: any expert's
        # descriptor of the same shapes will do.
        for copy in copies(mats[:n_mat], t, slot):
            copy.wait()

    real, fresh = read(_REAL) == 1, read(_FRESH) == 1
    e, nxt, slot0 = read(_EXPERT), read(_NEXT), read(_SLOT)

    @pl.when(read(_FIRST) == 1)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(real)
    def _():
        acc[...] = jnp.zeros(acc.shape, F32)

        def width_step(t, carry):
            slot = (slot0 + t) % 2
            last = t == width_tiles - 1
            # Copies to start, from ONE site (the dispatch over the held
            # arrays is the body's only part that grows with them):
            # 0, this tile, at the first step (no step before started
            # it); 1, the next tile of the stream, into the other slot.
            own = jnp.logical_and(s == 0, t == 0)
            ahead = jnp.logical_and(fresh, jnp.logical_or(
                jnp.logical_not(last), nxt >= 0))

            def issue(i, c):
                now = i == 0
                start(jnp.where(now, e, jnp.where(last, nxt, e)),
                      jnp.where(now, t, jnp.where(last, 0, t + 1)),
                      jnp.where(now, slot, 1 - slot))
                return c

            jax.lax.fori_loop(jnp.where(own, 0, 1), jnp.where(ahead, 2, 1),
                              issue, 0)

            @pl.when(fresh)
            def _():
                wait(t, slot)

            x = x_ref[...]
            h = expert_hidden(act, x, *(w[slot] for w in slots[:-1]))
            acc[...] += jnp.dot(h.astype(x.dtype), slots[-1][slot],
                                preferred_element_type=F32)
            return carry

        jax.lax.fori_loop(0, width_tiles, width_step, 0)
        row = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = jnp.logical_and(row >= read(_LO), row < read(_HI))
        o_ref[...] = jnp.where(mine, acc[...].astype(o_ref.dtype),
                               o_ref[...])


@functools.partial(jax.jit, static_argnames=('act', 'interpret'))
def _grouped_call(x_sorted, counts, experts, *, act, interpret):
    """Jitted on its own, as `pallas_paged._decode_call` is: a program
    of L expert layers traces and lowers the kernel once per operand
    shape, not L times (the compile cache is keyed on what lowering
    returns, so a warm start pays every lowering)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, dim = x_sorted.shape
    held, n_mat = len(experts), len(experts[0])
    width = experts[0][-1].shape[0]
    itemsize = jnp.dtype(x_sorted.dtype).itemsize
    tile = _width_tile(dim, width, n_mat, itemsize)
    width_tiles = width // tile
    block = _row_block(rows, itemsize)
    padded = -(-rows // block) * block
    x = x_sorted
    if padded != rows:
        x = jnp.pad(x_sorted, ((0, padded - rows), (0, 0)))
    blocks = padded // block
    # Visits are at most one a block plus one more for each expert
    # that starts inside a block another began.
    steps = blocks + held - 1
    plan = _plan(counts.astype(jnp.int32), block, blocks, steps,
                 width_tiles)

    def rows_of(k):
        return pl.BlockSpec((block, dim),
                            lambda s, p: (p[k * steps + s], 0))

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    w_dtype = experts[0][0].dtype
    slots = ([pltpu.VMEM((2, dim, tile), w_dtype)] * (n_mat - 1)
             + [pltpu.VMEM((2, tile, dim), w_dtype)])
    tile_bytes = block * dim * itemsize
    vmem = (2 * n_mat * dim * tile * jnp.dtype(w_dtype).itemsize
            + 4 * tile_bytes + 2 * block * dim * 4
            + n_mat * block * tile * 4 + (16 << 20))
    out = pl.pallas_call(
        functools.partial(_kernel, act, held, n_mat, width_tiles, steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[rows_of(_X_BLOCK)] + [anywhere] * (held * n_mat),
            out_specs=rows_of(_O_BLOCK),
            scratch_shapes=slots + [pltpu.VMEM((block, dim), F32),
                                    pltpu.SemaphoreType.DMA((2, n_mat))]),
        out_shape=jax.ShapeDtypeStruct((padded, dim), x_sorted.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=int(vmem)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='grouped_experts',
    )(plan, x, *(w for expert in experts for w in expert))
    return out if padded == rows else out[:rows]


def grouped_experts_pallas(x_sorted: jax.Array, counts: jax.Array,
                           experts: Sequence[Sequence[jax.Array]], *,
                           act: str, interpret: bool = False
                           ) -> jax.Array:
    """Experts over rows sorted by expert, dropless (route
    'grouped_pallas'), as `models/deepseek.grouped_experts` gives them.

    x_sorted [M, dim]: expert 0's `counts[0]` rows first, then expert
    1's, ...; rows past sum(counts) belong to nobody. experts: each
    held expert's matrices as the arrays they are, in the order
    `expert_hidden(act, ...)` and then `w_down` take them ([dim,
    width] ..., [width, dim]), of a shape `kernel_refusal` takes.
    Returns [M, dim] in x_sorted's dtype (rows past sum(counts):
    zeros)."""
    dim = x_sorted.shape[1]
    width = experts[0][-1].shape[0]
    refusal = kernel_refusal(dim, width, len(experts[0]), x_sorted.dtype)
    if refusal is None and ACTS.get(act) != len(experts[0]):
        refusal = f'{act!r} takes {ACTS.get(act)} matrices an expert'
    if refusal is not None:
        raise ValueError(f"route 'grouped_pallas': {refusal}")
    return _grouped_call(x_sorted, counts,
                         tuple(tuple(expert) for expert in experts),
                         act=act, interpret=interpret)
