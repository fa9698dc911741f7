"""Pipeline parallelism over a `stage` mesh axis via shard_map.

The TPU-native formulation (scaling-book recipe, not a port of the
reference's NCCL send/recv schedules): layer parameters are STACKED
([L, ...] leaves) and sharded over the mesh's `stage` axis, the
schedule runs inside ONE `shard_map`, and stage-to-stage transfer is
`lax.ppermute` (XLA collective-permute on ICI).

Two execution engines share that frame, selected by `schedule=`:

  gpipe (default)   the fused fill/drain scan: microbatch ingestion,
      per-stage layer application and activation hand-off are ONE
      `lax.scan`, and backward needs nothing hand-written — jax.grad
      differentiates through the scan and the ppermutes (a ppermute's
      transpose is the reverse ppermute), so the drain schedule falls
      out of AD. Every stage holds all M microbatch activations at
      the flush: memory O(M).

  1f1b / interleaved   the explicit-schedule runner: the op stream
      from parallel/pipeline_schedule.py (one chunk-forward or
      chunk-backward per stage per tick) executes under a
      `lax.switch` inside the tick scan, with hand-rolled backward —
      each backward op re-runs its chunk forward under `jax.vjp`
      from the stored chunk INPUT (per-chunk rematerialization) and
      accumulates parameter grads as it goes. 1F1B caps stored chunk
      inputs at S (vs GPipe's M): that memory headroom is what buys
      the larger microbatch counts that actually shrink the bubble
      fraction (S-1)/(M+S-1), and interleaved virtual stages divide
      the fraction by v on top. Collectives (vocab-parallel embed,
      head psum, the two ppermute rings) run UNCONDITIONALLY every
      tick — only the local chunk compute sits under the switch, so
      no device can diverge at a collective.

All schedules span 2(M*v + S - 1) ticks with 2(S - 1) bubble ticks
per device (see pipeline_schedule.py for the accounting the
step-metrics gauge and `bench.py --sweep-pipeline` report).

v2 (closes the v1 composition gaps):
  - tensor/fsdp/expert COMPOSE WITHIN STAGES: only `stage` and `data`
    are manual shard_map axes (`axis_names`); the rest stay under
    GSPMD, so stacked block leaves carry their usual logical-rule
    shardings (heads/mlp→tensor, embed→fsdp, expert→expert) on their
    inner dims and XLA inserts the within-stage collectives.
  - the embedding table and LM head are STAGE-SHARDED over the vocab
    dim (no longer replicated on every stage — the HBM that matters
    at 70B scale): embedding is a masked local gather + psum;
    the head is a vocab-parallel matmul with a psum/pmax logsumexp
    cross-entropy, which also spreads the head FLOPs across all
    stages instead of serializing them on the last one.
  - `num_layers % stages != 0` is allowed: the stack is zero-padded
    and padded slots are masked to identity in the per-stage scan.

Families: GPT, Llama, Mixtral (Mixtral's router aux loss is
accumulated across stages with live-tick masking; its batch-mean
products make the faithful reference the mean of per-microbatch
losses). Dropout is rejected (blocks run deterministically).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.parallel import pipeline_schedule as psched
from skypilot_tpu.parallel.train import TrainState


def stack_layer_params(params: Dict[str, Any], prefix: str,
                       num_layers: int,
                       pad_to: int = 0) -> Tuple[Any, Dict[str, Any]]:
    """Split a model's params into (stacked block leaves [L, ...],
    everything else). The stacked tree's structure is ONE block's.
    `pad_to > num_layers` zero-pads the stack (padded slots are
    masked to identity in the pipeline's per-stage scan)."""
    layers = [params[f'{prefix}{i}'] for i in range(num_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    if pad_to > num_layers:
        pad = pad_to - num_layers
        stacked = jax.tree.map(
            lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)),
            stacked)
    rest = {k: v for k, v in params.items()
            if not (k.startswith(prefix) and
                    k[len(prefix):].isdigit())}
    return stacked, rest


def unstack_layer_params(stacked: Any, rest: Dict[str, Any],
                         prefix: str, num_layers: int) -> Dict[str, Any]:
    """Inverse of stack_layer_params (checkpoint interop); ignores
    padded tail slots."""
    out = dict(rest)
    for i in range(num_layers):
        out[f'{prefix}{i}'] = jax.tree.map(lambda x, i=i: x[i], stacked)
    return out


def _vp_next_token_loss(local_logits: jax.Array, tokens: jax.Array,
                        stage: jax.Array, vshard: int,
                        vocab: int) -> jax.Array:
    """Vocab-parallel causal LM loss over the `stage` axis.

    local_logits: [B, S, vshard] — this stage's vocab shard (global
    column range [stage*vshard, (stage+1)*vshard), columns >= vocab
    are padding). Mirrors train.next_token_loss numerics: f32
    logsumexp with global-max subtraction (pmax), target logit via
    masked local gather + psum."""
    logits = local_logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    off = stage * vshard
    # Padded vocab columns must not contribute mass.
    valid = off + jnp.arange(vshard) < vocab
    logits = jnp.where(valid[None, None, :], logits, -jnp.inf)
    lid = targets - off
    ok = jnp.logical_and(lid >= 0, lid < vshard)
    tl = jnp.take_along_axis(
        logits, jnp.clip(lid, 0, vshard - 1)[..., None], axis=-1)[..., 0]
    target_logit = jax.lax.psum(jnp.where(ok, tl, 0.0), 'stage')
    # Global max: any m makes lse exact; stop_gradient keeps AD on the
    # softmax path (d lse/d logits = softmax regardless of m).
    # all_gather + max, not pmax: pmax has no differentiation rule
    # (even a zero tangent must flow through the primitive).
    m = jax.lax.stop_gradient(jnp.max(
        jax.lax.all_gather(jnp.max(logits, axis=-1), 'stage'), axis=0))
    se = jax.lax.psum(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), 'stage')
    lse = m + jnp.log(se)
    return jnp.mean(lse - target_logit)


class _Family(NamedTuple):
    """Per-model-family pipeline adapter.

    vocab_dims maps rest-leaf name -> the dim carrying the vocab
    (stage-sharded + padded to stages * vshard). embed_vp returns the
    (psum-combined) input embedding from the LOCAL vocab shard;
    head_local returns this stage's [B, S, vshard] logits slice."""
    prefix: str
    block: Any
    takes_positions: bool
    returns_aux: bool
    vocab_dims: Dict[str, int]
    embed_vp: Callable
    head_local: Callable


def _stage_psum(x: jax.Array) -> jax.Array:
    """psum over `stage`, carried in f32. Every caller has exactly ONE
    nonzero contributor (masked gather / masked broadcast), so the
    f32 round-trip is exact for bf16 inputs. Uniform f32 also keeps
    XLA's all-reduce combiner away from mixed bf16/f32 tuple
    all-reduces, whose dtype-rewrite pass crashes on CPU."""
    return jax.lax.psum(x.astype(jnp.float32), 'stage').astype(x.dtype)


def _vp_gather(table: jax.Array, tokens: jax.Array, stage: jax.Array,
               vshard: int) -> jax.Array:
    """Embedding lookup against this stage's vocab shard: gather the
    locally-owned rows (others masked to 0) and psum — exactly one
    stage owns each id, so the sum reassembles the global gather."""
    lid = tokens - stage * vshard
    ok = jnp.logical_and(lid >= 0, lid < vshard)
    x = table[jnp.clip(lid, 0, vshard - 1)]
    return _stage_psum(jnp.where(ok[..., None], x, 0))


def _gpt_embed_vp(rest, tokens, cfg, stage, vshard):
    x = _vp_gather(rest['wte'].astype(cfg.dtype), tokens, stage, vshard)
    return x + rest['wpe'].astype(cfg.dtype)[:tokens.shape[1]]


def _llama_embed_vp(rest, tokens, cfg, stage, vshard):
    return _vp_gather(rest['tok_embed'].astype(cfg.dtype), tokens,
                      stage, vshard)


def _family_of(model) -> _Family:
    # head_local reuses the models' own final_norm_logits helpers
    # unchanged: the vocab dim is only the einsum OUTPUT dim, so they
    # work on a local vocab shard as-is — and head/norm changes in the
    # model files cannot silently diverge from the pipelined path.
    from skypilot_tpu.models import gpt as gpt_lib
    from skypilot_tpu.models import llama as llama_lib
    from skypilot_tpu.models import mixtral as mixtral_lib
    if isinstance(model, gpt_lib.GPT):
        return _Family('h_', gpt_lib.Block(model.config), False, False,
                       {'wte': 0}, _gpt_embed_vp,
                       gpt_lib.final_norm_logits)
    if isinstance(model, llama_lib.Llama):
        return _Family('layer_', llama_lib.Block(model.config), True,
                       False, {'tok_embed': 0, 'lm_head': 1},
                       _llama_embed_vp, llama_lib.final_norm_logits)
    if isinstance(model, mixtral_lib.Mixtral):
        return _Family('layer_', mixtral_lib.Block(model.config), True,
                       True, {'tok_embed': 0, 'lm_head': 1},
                       _llama_embed_vp, llama_lib.final_norm_logits)
    from skypilot_tpu.models import deepseek as deepseek_lib
    if isinstance(model, deepseek_lib.Deepseek):
        # MLA blocks are llama-shaped at the pipeline seam (same
        # (x, positions) signature, same tok_embed/final_norm/lm_head
        # param layout, RMSNorm shared with llama) — the latent-KV
        # machinery is internal to the block.
        return _Family('layer_', deepseek_lib.Block(model.config), True,
                       False, {'tok_embed': 0, 'lm_head': 1},
                       _llama_embed_vp, llama_lib.final_norm_logits)
    raise ValueError(
        f'Pipeline parallelism supports the GPT, Llama, Mixtral, and '
        f'DeepSeek families; got {type(model).__name__}')


class PipelinedLM:
    """Pipeline-parallel training step (GPT/Llama/Mixtral/DeepSeek).

    Usage:
        pp = PipelinedLM(model, mesh, num_microbatches=8,
                         schedule='1f1b')
        stacked, rest = pp.split_params(params)
        loss = pp.loss(stacked, rest, tokens)          # jittable
        step = pp.make_train_step(tx)                  # optimizer step

    `schedule` picks the engine (module docstring): 'gpipe' is the
    fused scan + AD backward; '1f1b'/'interleaved' execute the
    explicit op stream from pipeline_schedule.make_schedule with
    hand-rolled backward. `virtual_stages` (interleaved only) is the
    number of layer chunks each device hosts.
    """

    def __init__(self, model, mesh: Mesh,
                 num_microbatches: int = 8,
                 remat_ticks: bool = True,
                 schedule: str = 'gpipe',
                 virtual_stages: int = 1) -> None:
        self.model = model
        self.cfg = model.config
        self.mesh = mesh
        self.num_stages = mesh.shape['stage']
        self.num_microbatches = num_microbatches
        # Rematerialize each schedule tick: backward recomputes the
        # tick's layer forwards instead of keeping every tick's
        # intermediate activations live — the memory profile pipeline
        # training needs (activations scale with ticks = M + S - 1
        # otherwise). Equality-tested on, off in test_pipeline.py.
        # (gpipe engine only: the explicit runner's backward ops
        # rematerialize per chunk by construction.)
        self.remat_ticks = remat_ticks
        self.family = _family_of(model)
        self._prefix = self.family.prefix
        if getattr(self.cfg, 'dropout_rate', 0.0):
            raise ValueError(
                'PipelinedLM runs blocks deterministically; '
                'dropout_rate > 0 would be silently ignored — train '
                'without dropout or use ShardedTrainer.')
        if getattr(self.cfg, 'remat', False):
            raise ValueError(
                'PipelinedLM does not rematerialize blocks; set '
                'remat=False (per-tick remat already bounds live '
                'activations — see remat_ticks).')
        S = self.num_stages
        # The schedule object validates style/virtual_stages/M and
        # carries the bubble/memory accounting even for gpipe (where
        # the fused scan executes the same logical stream).
        self.schedule_style = schedule
        self.virtual_stages = virtual_stages
        self.schedule = psched.make_schedule(
            S, num_microbatches, style=schedule,
            virtual_stages=virtual_stages)
        # Uneven layer counts pad the stack with masked identity slots
        # (the padded blocks' zero params stay zero: grads are masked,
        # so adamw never moves them). Chunking is per VIRTUAL stage:
        # each device hosts v chunks of layers_per_chunk layers.
        V = S * virtual_stages
        self.layers_per_chunk = -(-self.cfg.num_layers // V)
        self.layers_per_stage = self.layers_per_chunk * virtual_stages
        self.padded_layers = self.layers_per_chunk * V
        # Vocab is stage-sharded for the embedding/head; pad to S.
        self.vshard = -(-self.cfg.vocab_size // S)
        self.padded_vocab = self.vshard * S
        # Interleaving changes which layers live on which device:
        # device s hosts virtual stages s, S+s, ... — the stacked
        # array (contiguously stage-sharded) is PERMUTED so row
        # s*layers_per_stage + k*layers_per_chunk + l holds global
        # layer (k*S + s)*layers_per_chunk + l. Identity when v == 1.
        perm = np.empty(self.padded_layers, dtype=np.int64)
        pos = 0
        for s in range(S):
            for k in range(virtual_stages):
                vs = k * S + s
                for layer in range(self.layers_per_chunk):
                    perm[pos] = vs * self.layers_per_chunk + layer
                    pos += 1
        self._layer_perm = perm
        self._layer_perm_inv = np.argsort(perm)
        # Compiled explicit-schedule runners, keyed by seq_len.
        self._runner_cache: Dict[int, Callable] = {}

    # -- params -------------------------------------------------------------
    def _pad_vocab(self, rest: Dict[str, Any]) -> Dict[str, Any]:
        out = dict(rest)
        for name, dim in self.family.vocab_dims.items():
            leaf = out[name]
            pad = self.padded_vocab - leaf.shape[dim]
            if pad:
                widths = [(0, 0)] * leaf.ndim
                widths[dim] = (0, pad)
                out[name] = jnp.pad(leaf, widths)
        return out

    def _unpad_vocab(self, rest: Dict[str, Any]) -> Dict[str, Any]:
        out = dict(rest)
        for name, dim in self.family.vocab_dims.items():
            out[name] = jax.lax.slice_in_dim(
                out[name], 0, self.cfg.vocab_size, axis=dim)
        return out

    def split_params(self, params: Dict[str, Any]) -> Tuple[Any, Any]:
        stacked, rest = stack_layer_params(params, self._prefix,
                                           self.cfg.num_layers,
                                           pad_to=self.padded_layers)
        if self.virtual_stages > 1:
            perm = self._layer_perm
            stacked = jax.tree.map(lambda x: x[perm], stacked)
        return stacked, self._pad_vocab(rest)

    def merge_params(self, stacked: Any, rest: Any) -> Dict[str, Any]:
        if self.virtual_stages > 1:
            inv = self._layer_perm_inv
            stacked = jax.tree.map(lambda x: x[inv], stacked)
        return unstack_layer_params(stacked, self._unpad_vocab(rest),
                                    self._prefix, self.cfg.num_layers)

    def _rest_specs(self, rest: Dict[str, Any]) -> Dict[str, Any]:
        """Per-leaf PartitionSpecs for `rest`: vocab-dim leaves shard
        over `stage`; everything else (norm scales, wpe) replicates."""
        def spec_for(path, leaf):
            name = path[0].key if path else None
            if name in self.family.vocab_dims:
                dim = self.family.vocab_dims[name]
                entries = [None] * leaf.ndim
                entries[dim] = 'stage'
                return P(*entries)
            return P()

        return jax.tree_util.tree_map_with_path(spec_for, rest)

    def _block_mesh_specs(self, stacked: Any) -> Any:
        """Mesh-axis specs for stacked block leaves: 'stage' on the
        stack dim + the model's own logical rules (heads/mlp→tensor,
        embed→fsdp, expert→expert) on the inner dims — the
        within-stage sharding GSPMD executes under the auto axes."""
        import flax.linen as nn
        from flax import traverse_util
        from skypilot_tpu.parallel import mesh as mesh_lib
        rules = dict(mesh_lib.DEFAULT_RULES)

        abstract = jax.eval_shape(
            lambda: self.model.init(
                jax.random.PRNGKey(0),
                jnp.ones((1, 8), jnp.int32))['params'])
        logical = nn.get_partition_spec(abstract)
        block0 = traverse_util.flatten_dict(
            logical[f'{self._prefix}0'], sep='/')

        def map_axes(spec):
            entries = []
            for name in (spec or ()):
                ax = rules.get(name)
                axes = ax if isinstance(ax, tuple) else \
                    (ax,) if ax else ()
                axes = tuple(a for a in axes
                             if a in self.mesh.shape and a != 'stage')
                entries.append(axes if len(axes) > 1 else
                               (axes[0] if axes else None))
            return entries

        flat = traverse_util.flatten_dict(stacked, sep='/')
        out = {k: P('stage', *map_axes(block0.get(k)))
               for k in flat}
        return traverse_util.unflatten_dict(out, sep='/')

    def param_shardings(self, stacked: Any, rest: Any):
        """(stacked, rest) NamedShardings: layer dim over `stage` plus
        logical-rule inner-dim axes; rest vocab leaves over `stage`."""
        s_stage = jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self._block_mesh_specs(stacked),
            is_leaf=lambda x: isinstance(x, P))
        s_rest = jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self._rest_specs(rest),
            is_leaf=lambda x: isinstance(x, P))
        return s_stage, s_rest

    # -- forward ------------------------------------------------------------
    def loss(self, stacked: Any, rest: Any,
             tokens: jax.Array) -> jax.Array:
        """Mean LM loss over the global batch, pipeline-parallel.

        tokens: [global_batch, seq]; global_batch must divide into
        num_microbatches x data-axis size.

        With virtual_stages == 1 this runs the fused scan (schedule-
        independent math, differentiable with jax.grad — the gpipe
        engine and the oracle the explicit runner is tested against).
        Interleaved layouts delegate to the runner and return its
        loss (grads come from loss_and_grad, not jax.grad).
        """
        if self.virtual_stages > 1:
            return self.loss_and_grad(stacked, rest, tokens)[0]
        S = self.num_stages
        M = self.num_microbatches
        d = self.mesh.shape['data']
        B, seq_len = tokens.shape
        if B % (M * d):
            raise ValueError(f'batch {B} must divide into '
                             f'{M} microbatches x data={d}')
        mb = B // (M * d)
        tokens_mb = tokens.reshape(M, d * mb, seq_len)

        cfg = self.cfg
        fam = self.family
        block_apply = fam.block.apply
        lps = self.layers_per_stage
        true_layers = cfg.num_layers
        vshard = self.vshard
        remat_ticks = self.remat_ticks
        aux_scale = (cfg.router_aux_loss_weight /
                     cfg.num_layers) if fam.returns_aux else 0.0

        def pipeline(stacked_local, rest_local, tokens_local):
            # stacked_local: [layers_per_stage, ...] (stage shard);
            # rest_local: vocab leaves are this stage's shard;
            # tokens_local: [M, mb, seq] (data shard).
            stage = jax.lax.axis_index('stage')

            def apply_stage(x):
                aux0 = jnp.zeros((), jnp.float32)
                gidx = stage * lps + jnp.arange(lps)
                if fam.takes_positions:
                    positions = jnp.broadcast_to(
                        jnp.arange(x.shape[1]), x.shape[:2])

                def one_layer(carry, xs):
                    layer_params, li = xs
                    h, aux = carry
                    if fam.takes_positions:
                        out = block_apply({'params': layer_params}, h,
                                          positions)
                    else:
                        out = block_apply({'params': layer_params}, h,
                                          True)
                    if fam.returns_aux:
                        h2, a = out
                    else:
                        h2, a = out, jnp.zeros((), jnp.float32)
                    # Padded slots are identity (their zero params
                    # would not be, e.g. biased blocks) and aux-free.
                    real = li < true_layers
                    h2 = jnp.where(real, h2, h)
                    a = jnp.where(real, a, 0.0)
                    return (h2, aux + a), None

                (x, aux), _ = jax.lax.scan(one_layer, (x, aux0),
                                           (stacked_local, gidx))
                return x, aux

            def tick(carry, t):
                buf = carry
                in_idx = jnp.clip(t, 0, M - 1)
                # Stage-sharded embedding: every stage gathers its
                # vocab shard and a psum assembles the row (exact —
                # one shard owns each id). Only stage 0 consumes it.
                emb = fam.embed_vp(rest_local, tokens_local[in_idx],
                                   cfg, stage, vshard)
                x = jnp.where(stage == 0, emb.astype(buf.dtype), buf)
                y, aux = apply_stage(x)
                # A stage's tick is LIVE when it holds microbatch
                # t - stage in [0, M): bubble ticks process garbage
                # whose aux must not count.
                mb_idx = t - stage
                live = jnp.logical_and(mb_idx >= 0, mb_idx < M)
                aux = jnp.where(live, aux, 0.0)
                out_idx = t - (S - 1)
                live_out = jnp.logical_and(out_idx >= 0, out_idx < M)
                # Stage-sharded head: broadcast the last stage's
                # output (one psum), then every stage computes its
                # [.., vshard] logits slice — the head matmul runs
                # S-way parallel instead of serializing on the last
                # stage. Collectives run every tick (they cannot sit
                # under a per-stage cond); masking is via `where`.
                y_last = _stage_psum(
                    jnp.where(stage == S - 1, y, jnp.zeros_like(y)))
                local_logits = fam.head_local(rest_local, y_last, cfg)
                ce = _vp_next_token_loss(
                    local_logits,
                    tokens_local[jnp.clip(out_idx, 0, M - 1)],
                    stage, vshard, cfg.vocab_size)
                loss_mb = jnp.where(live_out, ce, 0.0)
                nxt = jax.lax.ppermute(
                    y, 'stage', [(i, (i + 1) % S) for i in range(S)])
                return nxt, (loss_mb, aux)

            buf0 = jnp.zeros((tokens_local.shape[1], seq_len,
                              cfg.embed_dim), cfg.dtype)
            body = (jax.checkpoint(tick, prevent_cse=False)
                    if remat_ticks else tick)
            _, (losses, auxes) = jax.lax.scan(body, buf0,
                                              jnp.arange(M + S - 1))
            # The CE terms are already psum-combined (identical on
            # every stage); aux is per-stage and must be summed.
            # Aux scaling matches the sequential model exactly
            # (weight * total_layers_aux / num_layers, averaged over
            # the M microbatches).
            total = jnp.sum(losses)
            total = total + aux_scale * jax.lax.psum(jnp.sum(auxes),
                                                     'stage')
            return jax.lax.pmean(total / M, 'data')

        fn = jax.shard_map(
            pipeline, mesh=self.mesh,
            in_specs=(jax.tree.map(lambda _: P('stage'), stacked),
                      self._rest_specs(rest),
                      P(None, 'data', None)),
            out_specs=P(),
            axis_names={'stage', 'data'},
            check_vma=False)
        # jit (inlined when already inside a jit): jax.checkpoint in
        # the tick body cannot be evaluated under an EAGER shard_map.
        return jax.jit(fn)(stacked, rest, tokens_mb)

    # -- explicit-schedule engine -------------------------------------------
    def loss_and_grad(self, stacked: Any, rest: Any, tokens: jax.Array,
                      scale: Any = None
                      ) -> Tuple[jax.Array, Tuple[Any, Any]]:
        """Loss AND (g_stacked, g_rest) in ONE pass of the explicit
        schedule: forwards and backwards interleave tick-by-tick per
        pipeline_schedule.make_schedule, so activation residency
        follows the schedule's accounting (1F1B: <= S chunk inputs
        per device) instead of GPipe's full-flush M.

        `scale` (default 1.0) multiplies every cotangent seed and the
        returned loss — the guard's loss_scale path: NaN here poisons
        loss and grads through the same arithmetic the isfinite
        predicate watches.
        """
        M = self.num_microbatches
        d = self.mesh.shape['data']
        B, seq_len = tokens.shape
        if B % (M * d):
            raise ValueError(f'batch {B} must divide into '
                             f'{M} microbatches x data={d}')
        mb = B // (M * d)
        tokens_mb = tokens.reshape(M, d * mb, seq_len)
        if scale is None:
            scale = 1.0
        fn = self._runner(seq_len)
        return fn(stacked, rest, tokens_mb,
                  jnp.asarray(scale, jnp.float32))

    def _runner(self, seq_len: int) -> Callable:
        if seq_len in self._runner_cache:
            return self._runner_cache[seq_len]
        S = self.num_stages
        M = self.num_microbatches
        v = self.virtual_stages
        V = S * v
        sched = self.schedule
        cfg = self.cfg
        fam = self.family
        block_apply = fam.block.apply
        Lc = self.layers_per_chunk
        true_layers = cfg.num_layers
        vshard = self.vshard
        aux_scale = (cfg.router_aux_loss_weight /
                     cfg.num_layers) if fam.returns_aux else 0.0
        T = sched.num_ticks
        tb = {k: jnp.asarray(t) for k, t in sched.tables.items()}
        act_depth = max(sched.live_peak_per_stage)
        FWD = psched.FWD
        stacked_specs, rest_specs = self._stack_rest_specs()
        # Replicated rest leaves (norm scales, wpe) get per-stage
        # local grad contributions that must be psum-combined; vocab-
        # sharded leaves already hold their shard's grad.
        rest_psum = jax.tree.map(
            lambda spec: not any(
                'stage' in (e if isinstance(e, tuple) else (e,))
                for e in spec),
            rest_specs, is_leaf=lambda x: isinstance(x, P))

        def apply_chunk(chunk_params, x, virt):
            """One chunk forward: Lc stacked layers starting at global
            layer virt*Lc; padded slots are masked to identity."""
            aux0 = jnp.zeros((), jnp.float32)
            gidx = virt * Lc + jnp.arange(Lc)
            if fam.takes_positions:
                positions = jnp.broadcast_to(
                    jnp.arange(x.shape[1]), x.shape[:2])

            def one_layer(carry, xs):
                layer_params, li = xs
                h, aux = carry
                if fam.takes_positions:
                    out = block_apply({'params': layer_params}, h,
                                      positions)
                else:
                    out = block_apply({'params': layer_params}, h,
                                      True)
                if fam.returns_aux:
                    h2, a = out
                else:
                    h2, a = out, jnp.zeros((), jnp.float32)
                real = li < true_layers
                h2 = jnp.where(real, h2, h)
                a = jnp.where(real, a, 0.0)
                return (h2, aux + a), None

            (y, aux), _ = jax.lax.scan(one_layer, (x, aux0),
                                       (chunk_params, gidx))
            return y, aux

        def pipeline(stacked_local, rest_local, tokens_local, scale):
            stage = jax.lax.axis_index('stage')
            mbsz = tokens_local.shape[1]
            # On jax 0.4.x shard_map, the transpose of psum is psum:
            # an inner jax.grad through the vocab-parallel loss hands
            # every psum path an S-times-replicated cotangent. The
            # probe measures the factor AT TRACE TIME (S under that
            # rule, 1 if a future jax transposes psum to identity) so
            # the explicit cotangent seeds stay calibrated either way.
            psum_t = jax.grad(
                lambda z: jax.lax.psum(z * z, 'stage') / 2.0)(
                    jnp.float32(1.0))
            chunked = jax.tree.map(
                lambda x: x.reshape(v, Lc, *x.shape[1:]), stacked_local)
            zeros_act = jnp.zeros((mbsz, seq_len, cfg.embed_dim),
                                  cfg.dtype)
            zero_chunk_grads = jax.tree.map(
                lambda x: jnp.zeros(x.shape[1:], jnp.float32), chunked)
            gacc_s0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), chunked)
            gacc_r0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), rest_local)

            def head_ce(y_last, r, tok):
                return _vp_next_token_loss(
                    fam.head_local(r, y_last, cfg), tok, stage,
                    vshard, cfg.vocab_size)

            def tick(carry, t):
                (act_buf, gy_buf, rxf, rxb, gacc_s, gacc_r, ce_sum,
                 aux_sum) = carry
                kind = tb['op_kind'][t, stage]
                chunk = jnp.clip(tb['op_chunk'][t, stage], 0, v - 1)
                virt = tb['op_virtual'][t, stage]
                aslot = jnp.clip(tb['act_slot'][t, stage], 0,
                                 act_depth - 1)
                # Vocab-parallel embedding for this tick's admission
                # (a collective: every stage gathers its shard and
                # psums; only a virtual-0 forward consumes it).
                emb_m = tb['embed_mb'][t]
                emb = fam.embed_vp(
                    rest_local,
                    tokens_local[jnp.clip(emb_m, 0, M - 1)], cfg,
                    stage, vshard)
                # Chunk inputs/cotangents for this tick's op.
                rxf_r = jnp.clip(tb['rxf_rslot'][t, stage], 0,
                                 sched.rx_fwd_depth - 1)
                rxb_r = jnp.clip(tb['rxb_rslot'][t, stage], 0,
                                 sched.rx_bwd_depth - 1)
                x_fwd = jnp.where(virt == 0, emb.astype(cfg.dtype),
                                  rxf[rxf_r])
                gy_r = jnp.clip(tb['gy_rslot'][t], 0,
                                sched.gy_depth - 1)
                g_in = jnp.where(virt == V - 1, gy_buf[gy_r],
                                 rxb[rxb_r])
                x_saved = act_buf[aslot]
                chunk_params = jax.tree.map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, chunk, 0, keepdims=False), chunked)
                aux_ct = (aux_scale * scale).astype(jnp.float32)

                def idle_fn(ops):
                    del ops
                    return zeros_act, zeros_act, zero_chunk_grads, \
                        jnp.zeros((), jnp.float32)

                def fwd_fn(ops):
                    cp, x_in, _, _ = ops
                    y, aux = apply_chunk(cp, x_in, virt)
                    return y, zeros_act, zero_chunk_grads, aux

                def bwd_fn(ops):
                    cp, _, x_stored, g = ops
                    _, vjp = jax.vjp(
                        lambda p, x: apply_chunk(p, x, virt), cp,
                        x_stored)
                    dp, dx = vjp((g, aux_ct))
                    dp = jax.tree.map(
                        lambda x: x.astype(jnp.float32), dp)
                    return zeros_act, dx.astype(cfg.dtype), dp, \
                        jnp.zeros((), jnp.float32)

                y_out, dx_out, dchunk, aux_term = jax.lax.switch(
                    kind, [idle_fn, fwd_fn, bwd_fn],
                    (chunk_params, x_fwd, x_saved, g_in))
                aux_sum = aux_sum + aux_term
                # Store this forward's chunk input for its backward
                # (bwd/idle rewrite the slot's current value: no-op).
                act_buf = jax.lax.dynamic_update_index_in_dim(
                    act_buf, jnp.where(kind == FWD, x_fwd, x_saved),
                    aslot, 0)
                gacc_s = jax.tree.map(
                    lambda acc, dg: acc.at[chunk].add(dg), gacc_s,
                    dchunk)
                # Vocab-parallel head + loss (collective, every tick):
                # broadcast the last virtual stage's fresh output, every
                # stage computes its logits shard, and the SUM of the
                # per-stage d(ce)/d(y_last) local grads is the true
                # cotangent for the one producer (psum transpose).
                is_last_fwd = jnp.logical_and(kind == FWD,
                                              virt == V - 1)
                y_last = _stage_psum(jnp.where(is_last_fwd, y_out,
                                               jnp.zeros_like(y_out)))
                gm = tb['gy_mb'][t]
                tok_m = tokens_local[jnp.clip(gm, 0, M - 1)]
                ce_m, (gy, d_rest_head) = jax.value_and_grad(
                    head_ce, argnums=(0, 1))(y_last, rest_local,
                                             tok_m)
                live = gm >= 0
                ce_sum = ce_sum + jnp.where(live, ce_m, 0.0)
                # Every head_ce path crosses exactly one psum, so the
                # per-device grads are psum_t-times their true partial
                # contribution; the true producer cotangent is the
                # cross-stage SUM of the partials.
                gy_full = jax.lax.psum(
                    gy.astype(jnp.float32), 'stage') * (scale /
                                                        psum_t)
                gy_w = jnp.clip(tb['gy_wslot'][t], 0,
                                sched.gy_depth - 1)
                gy_buf = jax.lax.dynamic_update_index_in_dim(
                    gy_buf,
                    jnp.where(live, gy_full.astype(cfg.dtype),
                              gy_buf[gy_w]), gy_w, 0)
                gacc_r = jax.tree.map(
                    lambda acc, dg: acc + jnp.where(
                        live,
                        dg.astype(jnp.float32) * (scale / psum_t),
                        0.0),
                    gacc_r, d_rest_head)
                # Embedding backward: a virtual-0 backward's dx is the
                # cotangent of the tick that embedded its microbatch.
                # The psum INSIDE embed_vp transposes to the broadcast,
                # so the unbroadcast per-device candidate is the right
                # seed (replicated leaves like wpe only charge stage 0).
                em = tb['embv_mb'][t]
                is_bwd_v0 = jnp.logical_and(kind == psched.BWD,
                                            virt == 0)
                dx_cand = jnp.where(is_bwd_v0, dx_out,
                                    jnp.zeros_like(dx_out))

                def embed_fn(r):
                    return fam.embed_vp(
                        r, tokens_local[jnp.clip(em, 0, M - 1)], cfg,
                        stage, vshard)

                _, evjp = jax.vjp(embed_fn, rest_local)
                d_rest_emb, = evjp(dx_cand.astype(emb.dtype))
                gacc_r = jax.tree.map(
                    lambda acc, dg: acc + jnp.where(
                        em >= 0, dg.astype(jnp.float32), 0.0),
                    gacc_r, d_rest_emb)
                # Ring hand-offs (every tick; receive-slot tables are
                # indexed by the PRODUCER so the consumer knows where
                # to park the message; -1 = nothing real arrived).
                msg_f = jax.lax.ppermute(
                    y_out, 'stage',
                    [(i, (i + 1) % S) for i in range(S)])
                wsf = tb['rxf_wslot'][t, (stage - 1) % S]
                wsf_c = jnp.clip(wsf, 0, sched.rx_fwd_depth - 1)
                rxf = jax.lax.dynamic_update_index_in_dim(
                    rxf, jnp.where(wsf >= 0, msg_f, rxf[wsf_c]),
                    wsf_c, 0)
                msg_b = jax.lax.ppermute(
                    dx_out, 'stage',
                    [(i, (i - 1) % S) for i in range(S)])
                wsb = tb['rxb_wslot'][t, (stage + 1) % S]
                wsb_c = jnp.clip(wsb, 0, sched.rx_bwd_depth - 1)
                rxb = jax.lax.dynamic_update_index_in_dim(
                    rxb, jnp.where(wsb >= 0, msg_b, rxb[wsb_c]),
                    wsb_c, 0)
                return (act_buf, gy_buf, rxf, rxb, gacc_s, gacc_r,
                        ce_sum, aux_sum), None

            carry0 = (
                jnp.zeros((act_depth, mbsz, seq_len, cfg.embed_dim),
                          cfg.dtype),
                jnp.zeros((sched.gy_depth, mbsz, seq_len,
                           cfg.embed_dim), cfg.dtype),
                jnp.zeros((sched.rx_fwd_depth, mbsz, seq_len,
                           cfg.embed_dim), cfg.dtype),
                jnp.zeros((sched.rx_bwd_depth, mbsz, seq_len,
                           cfg.embed_dim), cfg.dtype),
                gacc_s0, gacc_r0,
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32))
            (_, _, _, _, gacc_s, gacc_r, ce_sum, aux_sum), _ = \
                jax.lax.scan(tick, carry0, jnp.arange(T))
            total = ce_sum + aux_scale * jax.lax.psum(aux_sum,
                                                      'stage')
            loss = jax.lax.pmean(total / M, 'data') * scale
            g_stacked = jax.tree.map(
                lambda g, p: (jax.lax.pmean(g, 'data') / M)
                .reshape(p.shape).astype(p.dtype),
                gacc_s, stacked_local)
            g_rest = jax.tree.map(
                lambda g, p, needs: (
                    jax.lax.psum(g, 'stage') if needs else g)
                .astype(p.dtype),
                jax.tree.map(lambda g: jax.lax.pmean(g, 'data') / M,
                             gacc_r),
                rest_local, rest_psum)
            return loss, (g_stacked, g_rest)

        fn = jax.shard_map(
            pipeline, mesh=self.mesh,
            in_specs=(stacked_specs, rest_specs,
                      P(None, 'data', None), P()),
            out_specs=(P(), (stacked_specs, rest_specs)),
            axis_names={'stage', 'data'},
            check_vma=False)
        jitted = jax.jit(fn)
        self._runner_cache[seq_len] = jitted
        return jitted

    def _abstract_params(self) -> Dict[str, Any]:
        return self.model.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)
        )['params']

    def _stack_rest_specs(self) -> Tuple[Any, Any]:
        """(stacked, rest) manual-axis PartitionSpecs for shard_map."""
        import flax.linen as nn
        abstract = jax.eval_shape(
            lambda: self.split_params(
                nn.meta.unbox(self._abstract_params())))
        return (jax.tree.map(lambda _: P('stage'), abstract[0]),
                self._rest_specs(abstract[1]))

    # -- training -----------------------------------------------------------
    def init(self, rng: jax.Array, example: jax.Array,
             tx: optax.GradientTransformation) -> TrainState:
        """TrainState whose params are the (stacked, rest) pair, laid
        out with stage-sharded block leaves (+ logical-rule inner-dim
        shardings) and stage-sharded vocab tables."""
        import flax.linen as nn

        def _init():
            params = nn.meta.unbox(
                self.model.init(rng, example[:1])['params'])
            return self.split_params(params)

        # Born-sharded (the ShardedTrainer pattern): a model big
        # enough to NEED pipeline stages must never materialize whole
        # on one device.
        shapes = jax.eval_shape(_init)
        shardings = self.param_shardings(*shapes)
        stacked, rest = jax.jit(_init, out_shardings=shardings)()
        state = TrainState.create((stacked, rest), tx)
        # The scalar step (and any opt-state scalar, e.g. the schedule
        # count) must be MESH-replicated, not single-device: a
        # checkpoint restore follows this template's shardings, and
        # jit rejects mixed device sets.
        rep = NamedSharding(self.mesh, P())
        return state.replace(
            step=jax.device_put(state.step, rep),
            opt_state=jax.tree.map(
                lambda x: jax.device_put(x, rep)
                if getattr(x, 'ndim', None) == 0 else x,
                state.opt_state))

    def make_train_step(self, tx: optax.GradientTransformation,
                        guard: bool = False,
                        collect_grad_norm: bool = False):
        """The per-step train fn for the configured schedule.

        Unguarded: `(state, tokens) -> (state, loss)` — or
        `(state, (loss, grad_norm))` with `collect_grad_norm` (the
        --metrics-file twin of ShardedTrainer's). With `guard=True`:
        `(state, tokens, max_grad_norm, loss_scale) ->
        (state, (loss, grad_norm, bad))` — the NaN/spike verdict is
        computed on device from the GLOBAL loss and grad norm (GSPMD
        folds the per-stage shard contributions: the psum-of-
        per-stage-flags the schedule refactor exists to enable), and
        a bad step where-selects the old params/opt_state exactly
        like robustness/train_guard.py's sharded-trainer path.
        """
        collect = collect_grad_norm or guard
        use_runner = self.schedule_style != 'gpipe'

        def _loss_and_grads(stacked, rest, tokens, scale):
            if use_runner:
                return self.loss_and_grad(stacked, rest, tokens,
                                          scale=scale)
            return jax.value_and_grad(
                lambda s, r: self.loss(s, r, tokens) * scale,
                argnums=(0, 1))(stacked, rest)

        # Donating the state halves peak HBM (params + Adam moments
        # would otherwise be live twice per step).
        def _body(state: TrainState, tokens: jax.Array,
                  ctl: Optional[jax.Array] = None
                  ) -> Tuple[TrainState, Any]:
            stacked, rest = state.params
            scale = jnp.float32(1.0) if ctl is None else ctl[1]
            loss, grads = _loss_and_grads(stacked, rest, tokens,
                                          scale)
            gnorm = optax.global_norm(grads) if collect else None
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            if ctl is None:
                aux = loss if gnorm is None else (loss, gnorm)
                return state.replace(step=state.step + 1,
                                     params=params,
                                     opt_state=opt_state), aux
            bad = jnp.logical_or(
                jnp.logical_or(~jnp.isfinite(loss),
                               ~jnp.isfinite(gnorm)),
                gnorm > ctl[0])
            params = jax.tree.map(
                lambda new, old: jnp.where(bad, old, new),
                params, state.params)
            opt_state = jax.tree.map(
                lambda new, old: jnp.where(bad, old, new),
                opt_state, state.opt_state)
            return state.replace(step=state.step + 1, params=params,
                                 opt_state=opt_state), (loss, gnorm,
                                                        bad)

        step = jax.jit(_body, donate_argnums=(0,))
        if not guard:
            return step

        def guarded(state, tokens, max_grad_norm=float('inf'),
                    loss_scale=1.0):
            ctl = jnp.asarray([max_grad_norm, loss_scale],
                              dtype=jnp.float32)
            return step(state, tokens, ctl)

        return guarded


# Back-compat alias (the class predates Llama support).
PipelinedGPT = PipelinedLM
