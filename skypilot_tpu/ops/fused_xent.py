"""Fused blockwise LM-head + cross-entropy: no [B, S, V] logits, ever.

The training memory high-water mark of every recipe model is the
LM-head output — at Qwen2.5's 152k vocab the [B, S, V] logits tensor
dwarfs all activations combined and caps the per-chip batch
(parallel/train.py's naive `next_token_loss` materializes it twice:
forward logits + backward softmax). This op takes the final hidden
states [B, S, H] and the (possibly tied) head matrix instead, and
`lax.scan`s over vocab *chunks*: per chunk it forms [B, S, C] logits,
folds them into a running (max, sumexp) pair and the target-logit
gather, and discards them. A `jax.custom_vjp` makes the backward pass
blockwise too — softmax chunks are recomputed from the saved
logsumexp, so the residuals are just the hidden states (an activation
the model already keeps) and a [B, S] normalizer.

Peak temp memory for loss+backward drops from O(B*S*V) to
O(B*S*C) with C = the chunk size, autotuned at trace time from
{512, 1024, 2048, 4096} ∩ divisors(V) (largest candidate giving >= 4
chunks; when nothing divides V, the least-padding candidate is used
and the padded columns are masked out of the logsumexp). A vocab
small enough to fit in one chunk degenerates to the dense math —
identical compute AND identical numerics to the naive path, so tiny
smoke configs pay zero overhead.

Numerics: chunk matmuls run in the caller's compute dtype (bf16 on
the MXU) with f32 accumulation (`preferred_element_type`), and the
streaming logsumexp is f32 — the same precision contract as the naive
einsum + `jax.nn.logsumexp` path, so fp32 inputs match it to ~1e-7.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Chunk-size candidates, largest first (bigger chunks amortize the
# per-chunk scan overhead; smaller ones cut peak memory further).
BLOCK_CANDIDATES = (4096, 2048, 1024, 512)


def pick_block(vocab_size: int) -> int:
    """Trace-time chunk autotune over {512..4096} ∩ divisors(V).

    Prefers the largest candidate that divides V AND yields >= 4
    chunks (a real memory win); falls back to the largest plain
    divisor, then to the candidate that wastes the least padding
    (padded columns are masked inside the op).
    """
    divisors = [c for c in BLOCK_CANDIDATES if vocab_size % c == 0]
    for c in divisors:
        if vocab_size // c >= 4:
            return c
    if divisors:
        return divisors[0]
    return min(BLOCK_CANDIDATES,
               key=lambda c: ((-vocab_size) % c, -c))


def find_lm_head(params) -> Tuple[Any, bool]:
    """Locate a recipe model's LM head in its top-level params.

    Returns (weight, vocab_in_rows): GPT ties the head to the token
    embedding `wte` [V, H]; the Llama/Mixtral/DeepSeek families carry
    an untied `lm_head` [H, V].
    """
    if 'lm_head' in params:
        return params['lm_head'], False
    if 'wte' in params:
        return params['wte'], True
    raise ValueError(
        "no LM head found in params (expected top-level 'lm_head' "
        "or tied 'wte')")


def _chunked(w: jax.Array, block: int, vocab: int
             ) -> Tuple[jax.Array, jax.Array]:
    """[V, H] head -> ([n_chunks, block, H] rows, [n_chunks] starts),
    zero-padding the vocab dim up to a chunk multiple."""
    n_chunks = -(-vocab // block)
    v_pad = n_chunks * block
    if v_pad != vocab:
        w = jnp.pad(w, ((0, v_pad - vocab), (0, 0)))
    return (w.reshape(n_chunks, block, w.shape[-1]),
            jnp.arange(n_chunks, dtype=jnp.int32) * block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _blockwise_xent(block: int, vocab: int, x: jax.Array, w: jax.Array,
                    targets: jax.Array) -> jax.Array:
    """Per-token CE loss [B, T] from x [B, T, H], w [V, H] (vocab-major),
    targets [B, T] — without materializing [B, T, V]."""
    lse, tgt = _streaming_lse(block, vocab, x, w, targets)
    return lse - tgt


def _streaming_lse(block: int, vocab: int, x: jax.Array, w: jax.Array,
                   targets: jax.Array) -> Tuple[jax.Array, jax.Array]:
    w_chunks, starts = _chunked(w, block, vocab)
    b, t, _ = x.shape
    init = (jnp.full((b, t), -jnp.inf, jnp.float32),   # running max
            jnp.zeros((b, t), jnp.float32),            # running sumexp
            jnp.zeros((b, t), jnp.float32))            # target logit

    def body(carry, xs):
        m, s, tgt = carry
        w_c, start = xs
        logits = jnp.einsum('bth,ch->btc', x, w_c,
                            preferred_element_type=jnp.float32)
        valid = (start + jnp.arange(block)) < vocab
        logits = jnp.where(valid, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # exp(-inf - finite) = 0 exactly, so the first chunk (m=-inf,
        # s=0) and padded columns fold in without special cases.
        s = (s * jnp.exp(m - m_new) +
             jnp.sum(jnp.exp(logits - m_new[..., None]), axis=-1))
        local = targets - start
        hit = (local >= 0) & (local < block)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, block - 1)[..., None],
            axis=-1)[..., 0]
        tgt = jnp.where(hit, picked, tgt)
        return (m_new, s, tgt), None

    (m, s, tgt), _ = jax.lax.scan(body, init, (w_chunks, starts))
    return m + jnp.log(s), tgt


def _blockwise_fwd(block, vocab, x, w, targets):
    lse, tgt = _streaming_lse(block, vocab, x, w, targets)
    # Residuals: inputs (kept alive anyway) + the [B, T] normalizer.
    # Chunk logits/softmax are recomputed blockwise in the backward.
    return lse - tgt, (x, w, targets, lse)


def _blockwise_bwd(block, vocab, res, g):
    x, w, targets, lse = res
    w_chunks, starts = _chunked(w, block, vocab)
    cd = x.dtype  # backward matmuls ride the same (MXU) compute dtype

    def body(dx, xs):
        w_c, start = xs
        logits = jnp.einsum('bth,ch->btc', x, w_c,
                            preferred_element_type=jnp.float32)
        valid = (start + jnp.arange(block)) < vocab
        # Padded columns: exp(logit - lse) would be spurious; mask.
        p = jnp.where(valid, jnp.exp(logits - lse[..., None]), 0.0)
        local = targets - start
        hit = (local >= 0) & (local < block)
        onehot = (local[..., None] == jnp.arange(block)) & hit[..., None]
        d_logits = ((p - onehot.astype(jnp.float32)) *
                    g[..., None]).astype(cd)
        dx = dx + jnp.einsum('btc,ch->bth', d_logits, w_c,
                             preferred_element_type=jnp.float32)
        dw_c = jnp.einsum('btc,bth->ch', d_logits, x,
                          preferred_element_type=jnp.float32)
        return dx, dw_c

    dx, dw_chunks = jax.lax.scan(
        body, jnp.zeros(x.shape, jnp.float32), (w_chunks, starts))
    dw = dw_chunks.reshape(-1, w.shape[-1])[:vocab]
    # Integer targets take a float0 cotangent (the JAX convention for
    # non-differentiable inputs).
    dt = np.zeros(targets.shape, jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), dt


_blockwise_xent.defvjp(_blockwise_fwd, _blockwise_bwd)


@jax.named_scope('xent')
def fused_next_token_loss(hidden: jax.Array, weight: jax.Array,
                          tokens: jax.Array, *,
                          vocab_in_rows: Optional[bool] = None,
                          block_size: Optional[int] = None,
                          compute_dtype: Optional[Any] = None
                          ) -> jax.Array:
    """Causal-LM loss straight from final hidden states.

    Drop-in replacement for `head-matmul + next_token_loss`: predicts
    tokens[:, 1:] from hidden[:, :-1] @ head, mean (lse - target
    logit), but blockwise over the vocab so no [B, S, V] array exists
    in either pass.

    Args:
      hidden: [B, S, H] final (already normed) hidden states.
      weight: LM head — [V, H] when `vocab_in_rows` (tied embedding,
        GPT's `wte`) else [H, V] (untied `lm_head`). Inferred from
        shape when unambiguous.
      tokens: [B, S] int token ids.
      block_size: vocab chunk; None = `pick_block(V)` at trace time.
      compute_dtype: matmul operand dtype (None = hidden.dtype); the
        accumulation/loss dtype is always f32.
    """
    h_dim = hidden.shape[-1]
    if vocab_in_rows is None:
        rows = weight.shape[-1] == h_dim
        cols = weight.shape[0] == h_dim
        if rows == cols:
            raise ValueError(
                f'ambiguous head orientation for shape {weight.shape} '
                f'with H={h_dim}; pass vocab_in_rows explicitly')
        vocab_in_rows = rows
    w = weight if vocab_in_rows else weight.T
    vocab = w.shape[0]
    cd = compute_dtype or hidden.dtype
    w = w.astype(cd)
    targets = tokens[:, 1:]
    block = int(block_size) if block_size else pick_block(vocab)
    if block >= vocab:
        # Single chunk: the dense math is the blockwise math. Let
        # plain AD handle it — no recompute-in-backward overhead for
        # smoke-sized vocabs. Full-S matmul then slice (the power-of-2
        # seq length vectorizes better than S-1), logits in the
        # compute dtype with the upcast fused into the f32 logsumexp
        # reduction — step-for-step the naive `head + next_token_loss`
        # math.
        logits = jnp.einsum('bsh,vh->bsv', hidden.astype(cd), w,
                            preferred_element_type=cd)
        logits = logits[:, :-1].astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(lse - tgt)
    # Blockwise: the last position predicts nothing, so drop it BEFORE
    # the chunked matmuls (the naive path computes those logits and
    # throws them away; at 152k vocab that is real work).
    x = hidden[:, :-1].astype(cd)
    return jnp.mean(_blockwise_xent(block, vocab, x, w, targets))
