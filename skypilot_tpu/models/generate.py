"""Autoregressive generation with KV cache (serving compute path).

One jitted `lax.scan` drives both prefill and decode: at step t the
input token is the prompt token (teacher-forced) while t < prompt_len,
else the previously sampled token — KV cache carried as flax 'cache'
variables, so per-token cost is O(1) in sequence length. This is the
in-framework inference engine behind `serve` replicas
(`recipes/serve_lm.py`); continuous batching lands in a later round.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def filter_logits(logits: jax.Array, top_k: jax.Array,
                  top_p: jax.Array) -> jax.Array:
    """Per-row top-k / nucleus (top-p) filtering, fixed-shape.

    logits: [..., V]; top_k int32 [...] (0 = off); top_p f32 [...]
    (1.0 = off). Filtered entries become -inf. Standard caveats: ties
    at the k-th logit all survive; the nucleus always keeps at least
    the argmax."""
    vocab = logits.shape[-1]
    while top_k.ndim < logits.ndim - 1:
        top_k = top_k[..., None]
        top_p = top_p[..., None]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k - 1, 0, vocab - 1)[..., None],
        axis=-1)
    keep_k = jnp.where((top_k > 0)[..., None], logits >= kth, True)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Nucleus: keep a sorted token while the cumulative mass BEFORE it
    # is < p (the argmax always qualifies).
    sorted_keep = (cum - probs) < top_p[..., None]
    min_kept = jnp.min(jnp.where(sorted_keep, sorted_desc, jnp.inf),
                       axis=-1, keepdims=True)
    keep_p = jnp.where((top_p < 1.0)[..., None], logits >= min_kept,
                       True)
    return jnp.where(keep_k & keep_p, logits, -jnp.inf)


@jax.named_scope('sample')
def sample_tokens(rng: jax.Array, logits: jax.Array, temps: jax.Array,
                  top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Per-row sampling: greedy where temps == 0, else categorical
    over temperature-scaled, top-k/top-p-filtered logits. With
    top_k=0 and top_p=1 this consumes the SAME rng stream as plain
    categorical (no behavior change for existing callers)."""
    while temps.ndim < logits.ndim - 1:
        temps = temps[..., None]
    # The filter costs a vocab sort per step: cond skips it at runtime
    # whenever NO live slot uses top-k/top-p (the common case), so the
    # unfiltered path stays as fast as plain categorical.
    need_filter = jnp.logical_or(jnp.any(top_k > 0),
                                 jnp.any(top_p < 1.0))
    # Temperature FIRST, then nucleus (the HF/vLLM/OpenAI order): the
    # nucleus is computed over the temperature-scaled distribution, so
    # low temperature narrows the kept set. Top-k is scale-invariant.
    scaled = logits / jnp.maximum(temps, 1e-6)[..., None]
    filtered = jax.lax.cond(
        need_filter, lambda: filter_logits(scaled, top_k, top_p),
        lambda: scaled)
    sampled = jax.random.categorical(rng, filtered, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def make_generate_fn(model, max_total_len: int,
                     temperature: float = 0.0,
                     eos_id: Optional[int] = None):
    """Returns jitted fn(params, prompt[B,P], rng) -> tokens [B, T].

    Output rows are prompt ++ generated, padded with eos/0 after eos.
    """
    assert max_total_len <= model.config.max_seq_len

    @functools.partial(jax.jit, static_argnums=())
    def generate(params, prompt: jax.Array, rng: jax.Array) -> jax.Array:
        batch, prompt_len = prompt.shape
        cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
            positions=jnp.zeros((batch, 1), jnp.int32), decode=True,
        )['cache']
        import flax.linen as nn
        # init *ran* a step (junk K/V at position 0): reset.
        cache = jax.tree.map(jnp.zeros_like, nn.meta.unbox(cache))

        def step(carry, t):
            cache, prev_token, rng = carry
            # Input: prompt token while inside the prompt, else sampled.
            in_prompt = t < prompt_len
            tok = jnp.where(
                in_prompt,
                jax.lax.dynamic_index_in_dim(
                    prompt, jnp.minimum(t, prompt_len - 1), axis=1,
                    keepdims=False),
                prev_token)
            positions = jnp.full((batch, 1), t, jnp.int32)
            logits, mutated = model.apply(
                {'params': params, 'cache': cache},
                tok[:, None], positions=positions, decode=True,
                mutable=['cache'])
            logits = logits[:, 0]  # [B, V]
            rng, sub = jax.random.split(rng)
            if temperature > 0:
                sampled = jax.random.categorical(
                    sub, logits / temperature, axis=-1)
            else:
                sampled = jnp.argmax(logits, axis=-1)
            sampled = sampled.astype(jnp.int32)
            return (mutated['cache'], sampled, rng), sampled

        init_token = jnp.zeros((batch,), jnp.int32)
        (_, _, _), sampled_seq = jax.lax.scan(
            step, (cache, init_token, rng),
            jnp.arange(max_total_len - 1))
        sampled_seq = jnp.swapaxes(sampled_seq, 0, 1)  # [B, T-1]

        # Assemble: positions < prompt_len come from the prompt;
        # position p >= prompt_len is the sample from step p-1.
        out = jnp.zeros((batch, max_total_len), jnp.int32)
        out = jax.lax.dynamic_update_slice(out, prompt, (0, 0))
        positions = jnp.arange(max_total_len)[None, :]
        shifted = jnp.pad(sampled_seq, ((0, 0), (1, 0)))  # sample->pos+1
        out = jnp.where(positions >= prompt_len, shifted, out)

        if eos_id is not None:
            hit = jnp.cumsum(
                (out == eos_id) & (positions >= prompt_len), axis=1)
            keep = hit - ((out == eos_id) &
                          (positions >= prompt_len)).astype(hit.dtype) == 0
            out = jnp.where(keep, out, eos_id)
        return out

    return generate


def teacher_forced_logits(model, params, tokens: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Decode-mode logits for every position vs full-forward logits.

    Correctness harness: the cached incremental path must match the
    batched forward exactly (tests/unit_tests/test_generate.py).
    """
    batch, seq = tokens.shape
    full = model.apply({'params': params}, tokens)

    cache = model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        positions=jnp.zeros((batch, 1), jnp.int32), decode=True)['cache']
    import flax.linen as nn
    cache = jax.tree.map(jnp.zeros_like, nn.meta.unbox(cache))

    def step(cache, t):
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        positions = jnp.full((batch, 1), t, jnp.int32)
        logits, mutated = model.apply(
            {'params': params, 'cache': cache}, tok,
            positions=positions, decode=True, mutable=['cache'])
        return mutated['cache'], logits[:, 0]

    _, decoded = jax.lax.scan(step, cache, jnp.arange(seq))
    decoded = jnp.swapaxes(decoded, 0, 1)
    return full, decoded


def make_speculative_generate_fn(model, max_total_len: int,
                                 draft_k: int = 4, ngram: int = 2,
                                 eos_id: Optional[int] = None):
    """Greedy prompt-lookup speculative decoding.

    Drafts `draft_k` tokens per step by matching the last `ngram`
    generated tokens against earlier context (self-drafting — no draft
    model) and verifies the whole guess in ONE chunked forward pass
    through the cache (ops.chunked_cache_attention / the MLA absorbed
    chunk path). Accepted-prefix semantics make the output EXACTLY the
    greedy tokens of `make_generate_fn`, in between 1 and draft_k+1
    tokens per model call — large speedups on structured/repetitive
    text, never slower than +1 token per call. Greedy only (verification
    compares argmax); dense-cache models (paged pools not used here).

    Returns jitted fn(params, prompt [B, P], rng) -> tokens [B, T].
    """
    assert draft_k >= 1 and ngram >= 1
    # The verify chunk may write up to draft_k past the last kept token.
    assert max_total_len + draft_k + 1 <= model.config.max_seq_len + 1, (
        max_total_len, draft_k, model.config.max_seq_len)

    pad = draft_k + 1  # scratch tail so chunk writes stay in-bounds

    @jax.jit
    def generate(params, prompt: jax.Array, rng: jax.Array) -> jax.Array:
        del rng  # greedy
        batch, prompt_len = prompt.shape
        total = max_total_len + pad
        cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
            positions=jnp.zeros((batch, 1), jnp.int32), decode=True,
        )['cache']
        import flax.linen as nn
        cache = jax.tree.map(jnp.zeros_like, nn.meta.unbox(cache))

        tokens = jnp.zeros((batch, total), jnp.int32)
        tokens = jax.lax.dynamic_update_slice(tokens, prompt, (0, 0))

        # PREFILL: the whole prompt in one chunk; its last logits give
        # the first generated token. prefill=True: the cache is empty,
        # so attention stays chunk-local (flash-eligible).
        positions = jnp.broadcast_to(jnp.arange(prompt_len),
                                     (batch, prompt_len))
        logits, mutated = model.apply(
            {'params': params, 'cache': cache}, prompt,
            positions=positions, decode=True, mutable=['cache'],
            prefill=True)
        cache = mutated['cache']
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jax.vmap(
            lambda row, t: row.at[prompt_len].set(t))(tokens, first)
        length = jnp.full((batch,), prompt_len + 1, jnp.int32)

        # Sliding n-gram windows are recomputed per step from the
        # token buffer; windows fully inside the generated region only.
        n_windows = total - ngram  # window w covers [w, w+ngram)

        def draft(tokens_row, length_row):
            """Propose draft_k tokens following the most recent earlier
            occurrence of the row's trailing n-gram."""
            pattern = jax.lax.dynamic_slice(
                tokens_row, (length_row - ngram,), (ngram,))
            idx = jnp.arange(n_windows)
            windows = jnp.stack(
                [tokens_row[i:i + n_windows] for i in range(ngram)], -1)
            match = jnp.all(windows == pattern[None, :], axis=-1)
            # Only windows whose continuation starts before the tail:
            # w + ngram < length (strictly earlier occurrence).
            match &= idx + ngram < length_row
            any_match = jnp.any(match)
            w = jnp.where(match, idx, -1).max()
            src = jnp.where(any_match, w + ngram, length_row - 1)
            guess = jax.lax.dynamic_slice(tokens_row, (src,), (draft_k,))
            # No match: repeat the last token (worst case: 1 accept).
            last = tokens_row[length_row - 1]
            return jnp.where(any_match, guess,
                             jnp.full((draft_k,), last, jnp.int32))

        def cond(carry):
            tokens, cache, length = carry
            return jnp.any(length < max_total_len)

        def body(carry):
            tokens, cache, length = carry
            drafts = jax.vmap(draft)(tokens, length)        # [B, k]
            tokens = jax.vmap(
                lambda row, d, p: jax.lax.dynamic_update_slice(
                    row, d, (p,)))(tokens, drafts, length)
            # Verify chunk: [x_{L-1}, d_1..d_k] at positions L-1..L+k-1
            chunk = jax.vmap(
                lambda row, p: jax.lax.dynamic_slice(
                    row, (p - 1,), (draft_k + 1,)))(tokens, length)
            positions = (length - 1)[:, None] + jnp.arange(draft_k + 1)
            logits, mutated = model.apply(
                {'params': params, 'cache': cache}, chunk,
                positions=positions, decode=True, mutable=['cache'])
            cache = mutated['cache']
            y = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k+1]
            # Leading drafts matching the model's own greedy choice.
            accept = jnp.cumprod(
                (drafts == y[:, :-1]).astype(jnp.int32), axis=1)
            n_accept = accept.sum(axis=1)                       # [B]
            # Write the model's tokens (accepted prefix == drafts;
            # the first correction lands at L + n_accept).
            tokens = jax.vmap(
                lambda row, yy, p: jax.lax.dynamic_update_slice(
                    row, yy, (p,)))(tokens, y, length)
            advance = jnp.where(length < max_total_len,
                                n_accept + 1, 0)
            length = jnp.minimum(length + advance, max_total_len)
            return tokens, cache, length

        tokens, cache, length = jax.lax.while_loop(
            cond, body, (tokens, cache, length))
        out = tokens[:, :max_total_len]
        if eos_id is not None:
            positions = jnp.arange(max_total_len)[None, :]
            gen = positions >= prompt_len
            hit = jnp.cumsum((out == eos_id) & gen, axis=1)
            keep = hit - ((out == eos_id) & gen).astype(hit.dtype) == 0
            out = jnp.where(keep, out, eos_id)
        return out

    return generate
