"""`serve_open_loop`, with one more number compared: the share of the
scored positions at which the engine's greedy token falls more than
`score_position_nats` below the float32 reference's best.

Why. `shortfall_nats`, the worst position's gap, is set by the single
largest event among a run's thousand or two scored positions. In a
routed model of many small experts a bf16 pass and an int8 pass both
flip a near-tied last expert now and then, and a flip moves the logits
about as far in either: the worst position then reads nearly the same
in both (PERF.md, section 2), and no limit on it tells the precisions
apart. How OFTEN the engine's token falls off the reference's best
does: int8 rounding is some ten times bf16's, and it moves the token on
several times as many positions. A configuration that states
`score_off_best_share` (with `score_position_nats` and
`score_off_best_why`) is held to it beside `shortfall_nats`; one that
does not runs exactly as `serve_open_loop`.

Everything else is `serve_open_loop`'s: the run's process installs
three hooks on it and on `harness.finish`, and hands over to it. The
positions and their reference are the ones `score_rows` scores, read
once; `--control` reads the same share of the int8 pass on the same
rows (the reference's `control_gaps`)."""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import harness
from perfbench import manifest as manifest_lib

base = manifest_lib.driver('serve_open_loop')


def off_best_limit(cfg: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The share limit the configuration states, or None."""
    if 'score_off_best_share' not in cfg:
        return None
    share, nats = cfg['score_off_best_share'], cfg.get('score_position_nats')
    why = str(cfg.get('score_off_best_why') or '').strip()
    if not why:
        raise manifest_lib.ManifestError(
            'the configuration states score_off_best_share without '
            'score_off_best_why: a tolerance comes with its reason')
    for key, value, high in (('score_off_best_share', share, 1),
                             ('score_position_nats', nats, 10)):
        if isinstance(value, bool) or not isinstance(value, (int, float)) \
                or not 0 < value < high:
            raise manifest_lib.ManifestError(
                f'{key} {value!r} is out of (0, {high})')
    return {'share': float(share), 'nats': float(nats), 'why': why}


def shortfalls(reference, params, cfg: Dict[str, Any],
               row: Dict[str, Any], tokens: Optional[List[int]] = None
               ) -> np.ndarray:
    """Per scored position of `row`, how far below the reference's best
    `tokens` (the row's own by default) scores there."""
    lp = np.asarray(reference.log_probs(params, cfg, base._padded(row)))
    tokens = row['tokens'] if tokens is None else tokens
    first, last = row['prompt_tokens'], len(row['tokens'])
    lp = lp[first - 1:last - 1]
    chosen = lp[np.arange(last - first), np.asarray(tokens[first:last])]
    return lp.max(axis=-1) - chosen


def share_off(gaps: List[np.ndarray], nats: float) -> float:
    """The share of all positions whose gap exceeds `nats` (a position
    that is not finite counts as off)."""
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if not gaps.size:
        return 1.0
    return float(np.mean(~(np.isfinite(gaps) & (gaps <= nats))))


def install(cfg: Dict[str, Any]) -> None:
    """Hook `score_rows`, `control_reading` and `harness.finish` for this
    process, where the configuration states a share limit."""
    limit = off_best_limit(cfg)
    if limit is None:
        return
    seen: Dict[str, float] = {}

    def score_rows(cfg, params, rows, margin):
        reference = manifest_lib.reference(cfg['reference'])
        gaps = [shortfalls(reference, params, cfg, row) for row in rows]
        flat = np.concatenate(gaps) if gaps else np.zeros(0)
        worst = (float('inf') if not np.all(np.isfinite(flat))
                 else float(flat.max(initial=0.0)))
        seen['share'] = share_off(gaps, limit['nats'])
        return {'ok': bool(rows) and worst <= margin, 'rows': len(rows),
                'positions': int(flat.size), 'worst_shortfall_nats': worst,
                'margin': margin, 'off_best_share': seen['share'],
                'position_nats': limit['nats']}

    def control_reading(cfg, params, rows):
        reference = manifest_lib.reference(cfg['reference'])
        gaps = [np.asarray(reference.control_gaps(
            params, cfg, base._padded(row), row['prompt_tokens'],
            len(row['tokens']))) for row in rows]
        flat = np.concatenate(gaps) if gaps else np.zeros(0)
        return {'rows': len(rows),
                'control_shortfall_nats': float(flat.max(initial=0.0)),
                'control_off_best_share': share_off(gaps, limit['nats'])}

    def finish(ctx, *, compared, **kw):
        if 'share' in seen:
            compared = dict(compared, off_best_share={
                'value': seen['share'], 'at_most': limit['share']})
        return original_finish(ctx, compared=compared, **kw)

    original_finish = harness.finish
    base.score_rows, base.control_reading = score_rows, control_reading
    harness.finish = finish


def run(ctx: harness.Ctx) -> None:
    cfg = ctx.preset(ctx.config)
    limit = off_best_limit(cfg)
    if limit is not None:
        ctx.say(f'off-best share limit {limit["share"]:g} of the scored '
                f'positions, each off by more than {limit["nats"]:g} '
                f'nats: {limit["why"]}')
    install(cfg)
    base.run(ctx)
