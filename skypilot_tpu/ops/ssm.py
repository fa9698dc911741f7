"""Mamba-2's state-space recurrence for serving: a chunked scan for a
prefill chunk and a one-step update for a decode round. Plain XLA, but
for the decode round's route 'state_pallas' (ops/pallas_ssm.py).

A layer keeps, a SEQUENCE and not a token, one state h [H, P, N]
(heads x head size x state size, float32) and the last `conv_kernel -
1` inputs of its causal convolution. Per token, with A a negative
scalar a head, B and C [G, N] shared by the H / G heads of a group:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t
    y_t = h_t C_t + D x_t

`ssm_scan` takes S tokens from a given state in the SSD form: the
sequence in sub-chunks of `chunk_size`, inside one the quadratic
product sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j, between
them the carried state. It is exact for any S and any start state, and
every row has a valid length: a position past it has dt = 0, so it
neither decays nor feeds the state (exp(0) = 1, and the input term is
0), and `causal_conv` keeps it out of the convolution's tail.

`ssm_update` advances the live lanes of a decode round by one token:
the convolution over the slot's tail, silu, and the recurrence's step.
The state rows (4 MB a slot at the published widths) and the tails are
read and written in place, and a dead lane's rows are neither read nor
written: route 'state_pallas' (ops/pallas_ssm.py) walks the live rows
in one Pallas pass, route 'live_rows_loop' one live row a step of a
loop whose trip count is the number of live lanes (the CPU, a shape the
kernel refuses, and `kernel_check`'s reference).

A slot keeps its rows in the layout the kernel reads (`slot_shapes`):
the state as [P, N, H], heads on the lanes, so that dt x is a lane
vector and y = h C a sum over sublanes; the tail as [(K - 1) C / H,
H], the K - 1 last inputs one after the other in rows of H channels.
`state_to_slot`, `tail_to_slot` and their inverses convert a prefill
chunk's rows.

State, A, dt and every product of the recurrence are float32 at full
precision (a TPU's default float32 product rounds its operands to
bf16; the state is a running sum over the whole sequence).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST


def causal_conv(x: jax.Array, tail: jax.Array, weight: jax.Array,
                bias: Optional[jax.Array], lengths: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution of a chunk that continues a
    sequence.

    x [B, S, C] the chunk's inputs, tail [B, K - 1, C] the K - 1 inputs
    before it (zeros at a sequence's start), weight [K, C] (tap K - 1
    multiplies the current input), bias [C] (None: none, LFM2's short
    convolution), lengths i32[B] the valid
    inputs of each row. Returns (out [B, S, C] float32, the new tail
    [B, K - 1, C]: the last K - 1 inputs before position `lengths`, so
    a padded position never enters it)."""
    taps = weight.shape[0]
    seq = x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = None if bias is None else bias.astype(F32)
    for k in range(taps):
        term = full[:, k:k + seq].astype(F32) * weight[k].astype(F32)
        out = term if out is None else out + term
    new_tail = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, taps - 1, 0)
    )(full, lengths)
    return out, new_tail.astype(tail.dtype)


@jax.named_scope('ssm_scan')
def ssm_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, state: jax.Array,
             lengths: jax.Array, chunk_size: int
             ) -> Tuple[jax.Array, jax.Array]:
    """S tokens a row from `state`, in sub-chunks of `chunk_size`.

    x [B, S, H, P]; dt f32[B, S, H] (after softplus); a f32[H]
    (negative); b, c [B, S, G, N]; d f32[H]; state f32[B, H, P, N];
    lengths i32[B]: positions at and past it are padding. Returns
    (y f32[B, S, H, P], the state after each row's last valid token)."""
    batch, seq, heads, _ = x.shape
    groups = b.shape[2]
    q = chunk_size
    n_chunks = -(-seq // q)
    pad = n_chunks * q - seq
    valid = jnp.arange(seq)[None, :] < lengths[:, None]
    dt = jnp.where(valid[:, :, None], dt.astype(F32), 0.0)

    def chunks(t):
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        t = t.reshape((batch, n_chunks, q) + t.shape[2:])
        return jnp.moveaxis(t, 1, 0)

    x32, b32, c32 = x.astype(F32), b.astype(F32), c.astype(F32)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def step(h, xs):
        xq, dtq, bq, cq = xs                  # [B,Q,H,P] [B,Q,H] [B,Q,G,N]
        cum = jnp.cumsum(dtq * a, axis=1)                     # [B,Q,H]
        # exp(cum_i - cum_j) for j <= i; the rest would overflow.
        seg = cum[:, :, None, :] - cum[:, None, :, :]         # [B,i,j,H]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg,
                                  -jnp.inf))
        cb = jnp.einsum('bign,bjgn->bijg', cq, bq, precision=EXACT)
        w = (decay.reshape(batch, q, q, groups, heads // groups)
             * cb[..., None]).reshape(batch, q, q, heads)
        w = w * dtq[:, None, :, :]
        y = jnp.einsum('bijh,bjhp->bihp', w, xq, precision=EXACT)
        # The carried state's part: exp(cum_i) C_i . h.
        hg = h.reshape((batch, groups, heads // groups) + h.shape[2:])
        from_state = jnp.einsum('bign,bgkpn->bigkp', cq, hg,
                                precision=EXACT)
        y = y + (from_state.reshape(y.shape)
                 * jnp.exp(cum)[..., None])
        # The state after the sub-chunk.
        last = cum[:, -1]                                     # [B,H]
        feed = jnp.exp(last[:, None, :] - cum) * dtq          # [B,Q,H]
        bh = jnp.repeat(bq, heads // groups, axis=2)          # [B,Q,H,N]
        h = (h * jnp.exp(last)[:, :, None, None]
             + jnp.einsum('bjh,bjhp,bjhn->bhpn', feed, xq, bh,
                          precision=EXACT))
        return h, y

    state, y = jax.lax.scan(
        step, state.astype(F32),
        (chunks(x32), chunks(dt), chunks(b32), chunks(c32)))
    y = jnp.moveaxis(y, 0, 1).reshape(batch, n_chunks * q, heads, -1)
    y = y[:, :seq] + x32 * d.astype(F32)[None, None, :, None]
    return y, state


def slot_shapes(heads: int, hd: int, n: int, taps: int, width: int
                ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The shapes of one slot's rows: the state [P, N, H] (float32)
    and the convolution's tail [(K - 1) C / H, H] (`width` C channels,
    the K - 1 last inputs one after the other)."""
    if (taps - 1) * width % heads:
        raise ValueError(
            f'a tail of {taps - 1} x {width} channels does not fill rows '
            f'of {heads} (the heads)')
    return (hd, n, heads), ((taps - 1) * width // heads, heads)


def state_to_slot(h: jax.Array) -> jax.Array:
    """[..., H, P, N] -> the slot's [..., P, N, H]."""
    return jnp.moveaxis(h, -3, -1)


def state_from_slot(h: jax.Array) -> jax.Array:
    """The slot's [..., P, N, H] -> [..., H, P, N]."""
    return jnp.moveaxis(h, -1, -3)


def tail_to_slot(tail: jax.Array, heads: int) -> jax.Array:
    """[..., K - 1, C] -> the slot's [..., (K - 1) C / H, H]."""
    return tail.reshape(tail.shape[:-2] + (-1, heads))


def tail_from_slot(rows: jax.Array, taps: int) -> jax.Array:
    """The slot's [..., (K - 1) C / H, H] -> [..., K - 1, C]."""
    return rows.reshape(rows.shape[:-2] + (taps - 1, -1))


def conv_step(previous, xbc: jax.Array, weight, bias: jax.Array,
              dtype) -> jax.Array:
    """The convolution's step for one token, float32 sums in tap order,
    then silu, cast to `dtype`: previous the K - 1 last inputs (oldest
    first), xbc the token's, weight the K taps' float32 weights (tap K
    - 1 multiplies the token), bias float32; any matching shapes (both
    routes of `ssm_update` call it, so both add in the same order)."""
    acc = previous[0].astype(F32) * weight[0]
    for k in range(1, len(previous)):
        acc = acc + previous[k].astype(F32) * weight[k]
    acc = acc + xbc.astype(F32) * weight[len(previous)]
    return jax.nn.silu(bias + acc).astype(dtype)


def _one_step(h, x, dt, a, b, c, d):
    """One token of one row in the slot's layout: h f32[P, N, H], x
    [P, H], dt, a, d f32[H], b, c [G, N] -> (y f32[P, H], the new h)."""
    heads = h.shape[-1]
    groups = b.shape[0]
    bh = jnp.repeat(b.astype(F32), heads // groups, axis=0).T   # [N, H]
    ch = jnp.repeat(c.astype(F32), heads // groups, axis=0).T
    x32 = x.astype(F32)
    h = (h * jnp.exp(dt * a)[None, None, :]
         + bh[None, :, :] * (dt[None, :] * x32)[:, None, :])
    y = jnp.sum(h * ch[None, :, :], axis=1) + d[None, :] * x32
    return y, h


@jax.named_scope('ssm_update')
def ssm_update(state: jax.Array, tail: jax.Array, xbc: jax.Array,
               dt: jax.Array, a: jax.Array, d: jax.Array,
               weight: jax.Array, bias: jax.Array, live: jax.Array, *,
               groups: int, route: Optional[str] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a lane, for the live lanes; a lane is a slot.

    state f32[slots, P, N, H] and tail [slots, (K - 1) C / H, H] in the
    slot's layout (`slot_shapes`); xbc [slots, C] the token's input to
    the convolution (x, B and C before it: C = H P + 2 G N, the model's
    channel order); dt f32[slots, H]; a, d f32[H]; weight [K, C], bias
    [C]; live bool[slots]. A live lane's token goes through the
    convolution and silu, its tail is shifted by it, and its state is
    advanced. Returns (y f32[slots, H, P], zeros on a dead lane; the
    state and the tail, a live lane's rows updated in place and a dead
    lane's neither read nor written). The live rows are taken in
    `order`, live ones first; `route` (default: `pallas_ssm.
    resolve_route` of the shapes) says by what."""
    from skypilot_tpu.ops import pallas_ssm
    slots, hd, n, heads = state.shape
    taps, width = weight.shape
    inner, bc = heads * hd, groups * n
    if route is None:
        route = pallas_ssm.resolve_route(heads, hd, n, groups, width, taps,
                                         tail.dtype, slots)
    act_dtype = xbc.dtype
    a, d, dt = a.astype(F32), d.astype(F32), dt.astype(F32)
    xbc = xbc.astype(tail.dtype)
    w32, bias32 = weight.astype(F32), bias.astype(F32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    rows = jnp.sum(live, dtype=jnp.int32)
    if route == 'state_pallas':
        y, state, tail = pallas_ssm.state_update_pallas(
            state, tail, xbc.reshape(slots, width // heads, heads), dt, a,
            d, w32.reshape(taps, width // heads, heads),
            bias32.reshape(width // heads, heads), order, rows,
            groups=groups, act_dtype=act_dtype)
        return jnp.swapaxes(y, 1, 2), state, tail
    if route != 'live_rows_loop':
        raise ValueError(f'unknown state update route {route!r}')

    def step(i, carry):
        state, tail, y = carry
        r = order[i]
        previous = tail_from_slot(
            jax.lax.dynamic_index_in_dim(tail, r, 0, keepdims=False), taps)
        act = conv_step(previous, xbc[r], w32, bias32, act_dtype)
        h = jax.lax.dynamic_index_in_dim(state, r, 0, keepdims=False)
        y_r, h = _one_step(
            h, act[:inner].reshape(heads, hd).T, dt[r], a,
            act[inner:inner + bc].reshape(groups, n),
            act[inner + bc:].reshape(groups, n), d)
        shifted = jnp.concatenate([previous[1:], xbc[r][None]])
        state = jax.lax.dynamic_update_index_in_dim(state, h, r, 0)
        tail = jax.lax.dynamic_update_index_in_dim(
            tail, tail_to_slot(shifted, heads), r, 0)
        y = jax.lax.dynamic_update_index_in_dim(y, y_r, r, 0)
        return state, tail, y

    state, tail, y = jax.lax.fori_loop(
        0, rows, step, (state, tail, jnp.zeros((slots, hd, heads), F32)))
    return jnp.swapaxes(y, 1, 2), state, tail


def ssm_reference(x, dt, a, b, c, d, state, lengths):
    """The recurrence step by step over time (`lax.scan`), float32:
    what `ssm_scan` and `ssm_update` are checked against (the CPU tests
    and ops/kernel_check.py). Same arguments as `ssm_scan`."""
    heads, groups = x.shape[2], b.shape[2]
    valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
    dt = jnp.where(valid[:, :, None], dt.astype(F32), 0.0)

    def step(h, xs):
        xt, dtt, bt, ct = xs                        # [B,H,P] [B,H] [B,G,N]
        bh = jnp.repeat(bt, heads // groups, axis=1)
        ch = jnp.repeat(ct, heads // groups, axis=1)
        h = (h * jnp.exp(dtt * a)[:, :, None, None]
             + (dtt[:, :, None] * xt)[..., None] * bh[:, :, None, :])
        y = jnp.sum(h * ch[:, :, None, :], axis=-1) \
            + d[None, :, None] * xt
        return h, y

    time_major = lambda t: jnp.moveaxis(t.astype(F32), 1, 0)  # noqa: E731
    state, y = jax.lax.scan(
        step, state.astype(F32),
        (time_major(x), time_major(dt), time_major(b), time_major(c)))
    return jnp.moveaxis(y, 0, 1), state
