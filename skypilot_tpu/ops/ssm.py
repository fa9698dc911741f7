"""Mamba-2's state-space recurrence for serving: a chunked scan for a
prefill chunk and a one-step update for a decode round. Plain XLA.

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
The state rows ([slots, H, P, N], 4 MB a slot at the published widths)
and the tails are read and written in place, one live row a step of a
loop whose trip count is the number of live lanes: a dead lane's rows
are neither read nor written.

State, A, dt and every product of the recurrence are float32 at full
precision (a TPU's default float32 product rounds its operands to
bf16; the state is a running sum over the whole sequence).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST


def causal_conv(x: jax.Array, tail: jax.Array, weight: jax.Array,
                bias: jax.Array, lengths: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution of a chunk that continues a
    sequence.

    x [B, S, C] the chunk's inputs, tail [B, K - 1, C] the K - 1 inputs
    before it (zeros at a sequence's start), weight [K, C] (tap K - 1
    multiplies the current input), bias [C], lengths i32[B] the valid
    inputs of each row. Returns (out [B, S, C] float32, the new tail
    [B, K - 1, C]: the last K - 1 inputs before position `lengths`, so
    a padded position never enters it)."""
    taps = weight.shape[0]
    seq = x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = bias.astype(F32)
    for k in range(taps):
        out = out + full[:, k:k + seq].astype(F32) * weight[k].astype(F32)
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


def _one_step(h, x, dt, a, b, c, d):
    """One token of one row: h f32[H, P, N], x [H, P], dt f32[H],
    b, c [G, N] -> (y f32[H, P], the new h)."""
    heads = h.shape[0]
    groups = b.shape[0]
    bh = jnp.repeat(b.astype(F32), heads // groups, axis=0)    # [H, N]
    ch = jnp.repeat(c.astype(F32), heads // groups, axis=0)
    x32 = x.astype(F32)
    h = (h * jnp.exp(dt * a)[:, None, None]
         + (dt[:, None] * x32)[:, :, None] * bh[:, None, :])
    y = jnp.sum(h * ch[:, None, :], axis=-1) + d[:, None] * x32
    return y, h


@jax.named_scope('ssm_update')
def ssm_update(state: jax.Array, tail: jax.Array, xbc: jax.Array,
               dt: jax.Array, a: jax.Array, d: jax.Array,
               weight: jax.Array, bias: jax.Array, live: jax.Array, *,
               groups: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a lane, for the live lanes; a lane is a slot.

    state f32[slots, H, P, N]; tail [slots, (K - 1) * C], a slot's
    K - 1 last convolution inputs one after the other (two axes, so
    that XLA:TPU leaves the array's layout alone); xbc [slots, C] the
    token's input to the convolution (x, B and C before it: C = H P +
    2 G N); dt f32[slots, H]; a, d f32[H]; weight [K, C], bias [C];
    live bool[slots]. A live lane's token goes through the convolution
    and silu, its tail is shifted by it, and its state is advanced.
    Returns (y f32[slots, H, P], zeros on a dead lane; the state and
    the tail, a live lane's rows updated in place and a dead lane's
    neither read nor written). The live rows are taken first, one a
    step, by a loop whose trip count is their number."""
    slots, heads, hd, n = state.shape
    taps, width = weight.shape
    inner, bc = heads * hd, groups * n
    a, d, dt = a.astype(F32), d.astype(F32), dt.astype(F32)
    w32, bias32 = weight.astype(F32), bias.astype(F32)
    order = jnp.argsort(~live, stable=True)

    def step(i, carry):
        state, tail, y = carry
        r = order[i]
        row = jnp.concatenate([
            jax.lax.dynamic_index_in_dim(tail, r, 0, keepdims=False),
            xbc[r].astype(tail.dtype)])
        conv = bias32 + jnp.sum(
            row.reshape(taps, width).astype(F32) * w32, axis=0)
        act = jax.nn.silu(conv).astype(xbc.dtype)
        h = jax.lax.dynamic_index_in_dim(state, r, 0, keepdims=False)
        y_r, h = _one_step(
            h, act[:inner].reshape(heads, hd), dt[r], a,
            act[inner:inner + bc].reshape(groups, n),
            act[inner + bc:].reshape(groups, n), d)
        state = jax.lax.dynamic_update_index_in_dim(state, h, r, 0)
        tail = jax.lax.dynamic_update_index_in_dim(tail, row[width:], r, 0)
        y = jax.lax.dynamic_update_index_in_dim(y, y_r, r, 0)
        return state, tail, y

    state, tail, y = jax.lax.fori_loop(
        0, jnp.sum(live, dtype=jnp.int32), step,
        (state, tail, jnp.zeros((slots, heads, hd), F32)))
    return y, state, tail


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
