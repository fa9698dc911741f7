"""OpenAI-compatible completions/chat shims + SSE streaming.

The de-facto client contract: the reference's llm/ recipes serve vLLM
(/root/reference/llm/vllm/README.md:74,159 drives /v1/completions and
/v1/chat/completions), whose clients stream by default. Implements:

  - non-streaming completions with `n >= 1` (one-shot path batches
    the n samples into a single [n, P] generate call; the continuous
    engine fans out n slot submissions that decode concurrently);
  - SSE streaming (`stream: true`) with the OpenAI chunk schemas
    (`text_completion` chunks; `chat.completion.chunk` deltas), tokens
    flushed as the engine commits them;
  - incremental detokenization (UTF-8-safe: a token ending in a
    partial multi-byte sequence is held until complete);
  - stop-string scanning with holdback (text that could be the prefix
    of a stop string is not emitted until disambiguated).

Requests are executed through `InferenceRuntime`; HTTP writing goes
through the handler's small writer surface (send_json / sse_*).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from skypilot_tpu.inference.runtime import (InferenceRuntime,
                                            NoTokenizerError,
                                            iter_interleaved)


class TokenIdText:
    """Stands in for the tokenizer on /v1/completions when the model
    has none (a registry model with seeded random weights): text IS
    whitespace-separated decimal token ids, both ways. With it the
    OpenAI surface — streaming, `logprobs`, `echo` scoring — can be
    driven against any model the server can load, not only --hf
    checkpoints that ship tokenizer files."""

    def __call__(self, text: str) -> Dict[str, List[int]]:
        try:
            return {'input_ids': [int(t) for t in text.split()]}
        except ValueError:
            raise ValueError(
                'this model has no tokenizer: send `prompt` as an '
                'array of token ids (or as decimal ids separated by '
                'spaces); text prompts need a --hf checkpoint with '
                'tokenizer files') from None

    def decode(self, ids, skip_special_tokens: bool = False) -> str:
        del skip_special_tokens
        return ''.join(f' {int(i)}' for i in ids)


def completion_tokenizer(rt: InferenceRuntime):
    try:
        return rt.get_tokenizer()
    except NoTokenizerError:
        return TokenIdText()


def encode_prompt(rt: InferenceRuntime, tok, prompt) -> List[int]:
    """Token ids for one `prompt` entry: a string goes through the
    tokenizer; an array of token ids (the OpenAI contract allows
    both) is taken as is, after a range check."""
    if isinstance(prompt, str):
        return tok(prompt)['input_ids']
    ids = [int(t) for t in prompt]
    bad = [t for t in ids if not 0 <= t < rt.vocab_size]
    if bad:
        raise ValueError(f'token ids {bad[:4]} outside the vocabulary '
                         f'[0, {rt.vocab_size})')
    return ids


def normalize_prompts(prompt) -> list:
    """The four shapes OpenAI accepts for `prompt` -> a list of
    prompts, each a string or a list of token ids."""
    if isinstance(prompt, str):
        return [prompt]
    if isinstance(prompt, list) and prompt and \
            all(isinstance(t, int) for t in prompt):
        return [prompt]
    return list(prompt)


class IncrementalDecoder:
    """Streamed token ids -> text deltas.

    Decodes the full generated-id prefix each push (O(n) per token —
    fine at serving lengths; HF's streamer uses the same shape) and
    emits only the new suffix. A trailing U+FFFD means the byte-level
    BPE stream ends mid-codepoint: hold until the next token completes
    it."""

    def __init__(self, tok) -> None:
        self.tok = tok
        self.ids: List[int] = []
        self.text = ''

    def push(self, tok_id: int) -> str:
        self.ids.append(tok_id)
        full = self.tok.decode(self.ids, skip_special_tokens=True)
        if full.endswith('�'):
            return ''
        delta = full[len(self.text):]
        self.text = full
        return delta

    def flush(self) -> str:
        """Final delta (drops an unresolved partial codepoint)."""
        full = self.tok.decode(self.ids, skip_special_tokens=True)
        if full.endswith('�'):
            full = full[:-1]
        delta = full[len(self.text):]
        self.text = full
        return delta


class StopStringScanner:
    """Emit-safe streaming with OpenAI `stop` semantics: the completion
    ends BEFORE the first occurrence of any stop string, and no text
    that might turn out to be part of one is ever emitted early."""

    def __init__(self, stops: List[str]) -> None:
        self.stops = [s for s in stops if s]
        self.buf = ''
        self.emitted = 0
        self.hit = False

    def _holdback(self) -> int:
        """Length of the longest buffer suffix that is a proper prefix
        of some stop string (must not be emitted yet)."""
        hold = 0
        for s in self.stops:
            for k in range(min(len(s) - 1, len(self.buf)), 0, -1):
                if self.buf.endswith(s[:k]):
                    hold = max(hold, k)
                    break
        return hold

    def push(self, delta: str) -> str:
        """Returns the newly emittable text; sets `hit` when a stop
        string landed (emittable text ends right before it)."""
        if self.hit:
            return ''
        self.buf += delta
        cut = -1
        for s in self.stops:
            i = self.buf.find(s)
            if i != -1:
                cut = i if cut == -1 else min(cut, i)
        if cut != -1:
            self.hit = True
            out = self.buf[self.emitted:cut]
            self.emitted = cut
            return out
        safe = len(self.buf) - self._holdback()
        out = self.buf[self.emitted:safe]
        self.emitted = max(self.emitted, safe)
        return out

    def flush(self) -> str:
        if self.hit:
            return ''
        out = self.buf[self.emitted:]
        self.emitted = len(self.buf)
        return out


def trim_stops(text: str, stops: List[str]) -> Tuple[str, bool]:
    cut = -1
    for s in stops:
        if not s:
            continue
        i = text.find(s)
        if i != -1:
            cut = i if cut == -1 else min(cut, i)
    if cut != -1:
        return text[:cut], True
    return text, False


class CompletionRequest:
    """Validated, normalized body shared by both /v1 endpoints."""

    def __init__(self, prompts: list, max_new: int,
                 temperature: float, top_p: float,
                 stop_strings, n: int, stream: bool,
                 logprobs: Optional[int] = None,
                 echo: bool = False,
                 deadline_s: float = 600.0,
                 adapter: Optional[str] = None,
                 model: Optional[str] = None) -> None:
        if isinstance(stop_strings, str):
            stop_strings = [stop_strings]
        if logprobs is not None and adapter is not None:
            raise ValueError(
                'logprobs with an adapter model is not supported '
                '(the scoring pass runs base weights)')
        if n < 1 or n > 16:
            raise ValueError(f'n must be in [1, 16], got {n}')
        if stream and len(prompts) != 1:
            raise ValueError(
                'stream=true supports a single prompt per request')
        if logprobs is not None:
            logprobs = int(logprobs)
            if not 0 <= logprobs <= 5:
                raise ValueError(
                    f'logprobs must be in [0, 5], got {logprobs}')
            if stream:
                raise ValueError(
                    'logprobs with stream=true is not supported')
        if echo and logprobs is None:
            raise ValueError('echo requires logprobs')
        self.prompts = prompts
        self.max_new = max_new
        self.temperature = temperature
        self.top_p = top_p
        self.stop_strings = list(stop_strings or [])
        self.n = n
        self.stream = stream
        self.logprobs = logprobs
        self.echo = echo
        # Per-request deadline, seconds (the server clamps the body's
        # `timeout` field into (0, --request-timeout]); propagated to
        # engine slots so an expired request is reaped mid-decode.
        self.deadline_s = float(deadline_s)
        # `model` field: the resolved adapter (None = base) and the
        # name to echo in responses (the OpenAI contract reports the
        # REQUESTED model, not always the base).
        self.adapter = adapter
        self.model = model


def _logprobs_block(rt: InferenceRuntime, tok, row: List[int],
                    n_top: int, echo: bool,
                    prompt_len: int) -> Dict[str, object]:
    """The OpenAI completions `logprobs` object for one choice:
    per-token logprob + top-N alternatives + text offsets, computed
    by ONE teacher-forced scoring pass (deterministic model — the
    values equal what decode produced). With `echo`, prompt tokens
    are covered too (position 0 scores as null)."""
    import numpy as np
    lp = rt.score_logprobs(row)                  # [T, vocab]
    start = 0 if echo else prompt_len
    tokens, token_logprobs, top_logprobs, offsets = [], [], [], []
    offset = 0
    for i in range(start, len(row)):
        piece = tok.decode([row[i]])
        tokens.append(piece)
        offsets.append(offset)
        offset += len(piece)
        if i == 0:
            token_logprobs.append(None)
            top_logprobs.append(None)
            continue
        token_logprobs.append(round(float(lp[i - 1, row[i]]), 5))
        if n_top > 0:
            idx = np.argsort(lp[i - 1])[::-1][:n_top]
            top_logprobs.append(
                {tok.decode([int(t)]): round(float(lp[i - 1, t]), 5)
                 for t in idx})
        else:
            top_logprobs.append({})
    return {'tokens': tokens, 'token_logprobs': token_logprobs,
            'top_logprobs': top_logprobs, 'text_offset': offsets}


def run_completion(rt: InferenceRuntime, req: CompletionRequest
                   ) -> Dict[str, object]:
    """Non-streaming completions: returns the OpenAI response dict.
    Each prompt yields `n` choices (indices p*n..p*n+n-1, the OpenAI
    layout for multi-prompt + n)."""
    tok = completion_tokenizer(rt)
    t0 = time.monotonic()
    encoded = [encode_prompt(rt, tok, p) for p in req.prompts]
    limit = rt.limit_for(req.temperature)
    for ids in encoded:
        if len(ids) >= limit:
            raise ValueError(f'prompt tokenizes to {len(ids)} >= '
                             f'max_total_len {limit}')
    rows: List[List[int]] = []
    row_prompt: List[List[int]] = []  # prompt ids per output row
    ttft: Optional[float] = None      # engine path latches first commit
    engine = rt.engine_for(req.adapter)
    if req.max_new <= 0:
        # Scoring mode (echo + logprobs + max_tokens=0 — the eval-
        # harness contract): no generation at all.
        for ids in encoded:
            for _ in range(req.n):
                rows.append(list(ids))
                row_prompt.append(ids)
    elif engine is not None:
        from skypilot_tpu.observability.catalog import FirstTokenLatch
        latch = FirstTokenLatch()  # non-streaming TTFT: first commit
        futs = []
        try:
            for ids in encoded:
                for _ in range(req.n):
                    futs.append(engine.submit(
                        ids, max_new_tokens=req.max_new,
                        temperature=req.temperature, top_p=req.top_p,
                        on_token=latch, deadline_s=req.deadline_s,
                        adapter=req.adapter))
                    row_prompt.append(ids)
        except Exception:
            # A shed submission mid-fan-out: cancel the admitted
            # siblings (they would decode for a 429'd client).
            if futs:
                engine.cancel(futs)
            raise
        # Expired requests resolve with DeadlineExceededError from the
        # engine's reaper; the host timeout is only a backstop.
        rows = [f.result(timeout=req.deadline_s + 30.0) for f in futs]
        ttft = latch.first_token_s
    else:
        import jax
        import jax.numpy as jnp
        for ids in encoded:
            # The n samples batch into ONE [n, P] generate call —
            # categorical sampling is independent per row, so this is
            # the n>1 fan-out at full MXU utilization (greedy rows are
            # identical by definition, as in the OpenAI contract).
            want = len(ids) + req.max_new
            bucket = 8
            while bucket < want:
                bucket *= 2
            bucket = min(bucket, limit)
            fn = rt.get_fn(req.n, req.temperature, bucket)
            out = fn(rt.params,
                     jnp.asarray([ids] * req.n, jnp.int32),
                     rt.split_rng())
            got = jax.device_get(out)
            for r in range(req.n):
                rows.append(got[r][:min(want, bucket)].tolist())
                row_prompt.append(ids)

    choices = []
    total_completion = 0
    for i, (ids, row) in enumerate(zip(row_prompt, rows)):
        text = tok.decode(row[len(ids):], skip_special_tokens=True)
        n_gen = len(row) - len(ids)
        finish = 'length' if n_gen >= req.max_new else 'stop'
        text, hit = trim_stops(text, req.stop_strings)
        if hit:
            finish = 'stop'
        total_completion += n_gen
        lp_block = None
        if req.logprobs is not None:
            lp_block = _logprobs_block(rt, tok, row, req.logprobs,
                                       req.echo, len(ids))
        if req.echo:
            text = tok.decode(ids, skip_special_tokens=True) + text
        choices.append({'index': i, 'text': text,
                        'finish_reason': finish,
                        'logprobs': lp_block})
    # Usage counts each PROMPT once (the OpenAI contract): row_prompt
    # holds one entry per choice, so summing it would over-report the
    # prompt n× under n>1.
    total_prompt = sum(len(ids) for ids in encoded)
    rt.metrics.record(time.monotonic() - t0, total_completion,
                      ttft_s=ttft, n_prompt_tokens=total_prompt)
    return {
        'object': 'text_completion',
        'model': req.model or rt.model_name,
        'choices': choices,
        'usage': {
            'prompt_tokens': total_prompt,
            'completion_tokens': total_completion,
            'total_tokens': total_prompt + total_completion,
        },
    }


def stream_completion(rt: InferenceRuntime, req: CompletionRequest,
                      writer, chat: bool = False) -> None:
    """SSE streaming for one prompt x n choices.

    Chunks follow the OpenAI schemas: `text_completion` chunks with
    incremental `text`, or `chat.completion.chunk` deltas ({'role'}
    first, then {'content': ...}) when `chat`. The n choices decode
    CONCURRENTLY (engine slots); their chunks interleave by arrival,
    each tagged with its choice index. Ends with per-choice
    finish_reason chunks and `data: [DONE]`."""
    tok = completion_tokenizer(rt)
    ids = encode_prompt(rt, tok, req.prompts[0])
    limit = rt.limit_for(req.temperature, streaming=True)
    if len(ids) >= limit:
        raise ValueError(f'prompt tokenizes to {len(ids)} >= '
                         f'max_total_len {limit}')
    t0 = time.monotonic()
    handles = [rt.submit_stream(ids, req.max_new, req.temperature,
                                top_p=req.top_p,
                                deadline_s=req.deadline_s,
                                adapter=req.adapter)
               for _ in range(req.n)]
    writer.sse_start(handles)
    obj = 'chat.completion.chunk' if chat else 'text_completion'
    model_name = req.model or rt.model_name

    def chunk(index: int, text: Optional[str],
              finish: Optional[str] = None) -> Dict[str, object]:
        c: Dict[str, object] = {'index': index,
                                'finish_reason': finish}
        if chat:
            c['delta'] = {} if text is None else {'content': text}
        else:
            c['text'] = text or ''
            c['logprobs'] = None
        return {'object': obj, 'model': model_name,
                'choices': [c]}

    if chat:
        for i in range(req.n):
            writer.sse_send({'object': obj, 'model': model_name,
                             'choices': [{'index': i,
                                          'delta': {'role': 'assistant'},
                                          'finish_reason': None}]})

    decs = [IncrementalDecoder(tok) for _ in range(req.n)]
    scans = [StopStringScanner(req.stop_strings) for _ in range(req.n)]
    n_gen = [0] * req.n
    ttft: Optional[float] = None
    # ITL records at engine commit time (StreamHandle.on_token).

    try:
        for i, t in iter_interleaved(handles):
            if ttft is None:
                ttft = time.monotonic() - t0
            n_gen[i] += 1
            if scans[i].hit:
                continue  # post-stop tokens: drop
            out = scans[i].push(decs[i].push(t))
            if out:
                writer.sse_send(chunk(i, out))
    finally:
        # Disconnected consumer: free the slots NOW instead of
        # decoding tokens nobody reads (no-op on normal completion).
        rt.cancel_streams(handles)
    for i in range(req.n):
        if not scans[i].hit:
            out = scans[i].push(decs[i].flush()) + scans[i].flush()
            if out:
                writer.sse_send(chunk(i, out))
        finish = ('stop' if scans[i].hit
                  else 'length' if n_gen[i] >= req.max_new else 'stop')
        writer.sse_send(chunk(i, None, finish))
    writer.sse_done()
    rt.metrics.record(time.monotonic() - t0, sum(n_gen), ttft_s=ttft,
                      n_prompt_tokens=len(ids))


_warned_no_template = False


def _warn_no_template(reason: str) -> None:
    global _warned_no_template
    if not _warned_no_template:
        _warned_no_template = True
        import sys
        print(f'openai_compat: tokenizer has no usable chat template '
              f'({reason}); falling back to "role: content" prompts.',
              file=sys.stderr, flush=True)


def render_chat_prompt(rt: InferenceRuntime, messages) -> str:
    """Chat template when the checkpoint ships one, else a transparent
    `role: content` fallback (beats a 400 for base models)."""
    tok = rt.get_tokenizer()
    try:
        return tok.apply_chat_template(messages, tokenize=False,
                                       add_generation_prompt=True)
    except Exception as e:  # pylint: disable=broad-except
        # Base models ship no template; say so once instead of letting
        # users puzzle over oddly formatted completions.
        _warn_no_template(f'{type(e).__name__}: {e}')
        return '\n'.join(f"{m['role']}: {m['content']}"
                         for m in messages) + '\nassistant:'


def to_chat_response(out: Dict[str, object]) -> Dict[str, object]:
    out['object'] = 'chat.completion'
    for c in out['choices']:
        c['message'] = {'role': 'assistant', 'content': c.pop('text')}
        lp = c.get('logprobs')
        if lp:
            # Legacy completions block -> modern chat format
            # ({content: [{token, logprob, bytes, top_logprobs}]}).
            content = []
            for token, logprob, top in zip(lp['tokens'],
                                           lp['token_logprobs'],
                                           lp['top_logprobs']):
                content.append({
                    'token': token,
                    'logprob': logprob,
                    'bytes': list(token.encode()),
                    'top_logprobs': [
                        {'token': t, 'logprob': v,
                         'bytes': list(t.encode())}
                        for t, v in sorted((top or {}).items(),
                                           key=lambda kv: -kv[1])],
                })
            c['logprobs'] = {'content': content}
    return out
