"""Sharded training harness: init + train step compiled over a mesh.

The pattern ("How to Scale Your Model" recipe): annotate arrays with
logical axes in the model, map logical→mesh with a rules table, give
jit the in/out shardings, and let XLA GSPMD insert the ICI/DCN
collectives. No hand-written collectives in the train loop.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.models import lora as lora_lib
from skypilot_tpu.ops import fused_xent
from skypilot_tpu.parallel import mesh as mesh_lib


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params: Any, tx: optax.GradientTransformation
               ) -> 'TrainState':
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=tx.init(params))


def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Causal LM loss: predict tokens[:, 1:] from logits[:, :-1].

    logsumexp form: only the [B,S] target logits and the [B,S]
    normalizer survive — no second [B,S,V] log-prob array in HBM
    (the [B,S,V] logits are already the memory high-water mark).
    """
    # Upcast once: bf16 logits (the memory-lean LM-head option) get an
    # f32 logsumexp; XLA fuses the convert into the reduction, so no
    # f32 [B,S,V] array ever lands in HBM.
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    target_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - target_logit)


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      grad_clip: float = 1.0,
                      warmup_steps: int = 0,
                      total_steps: Optional[int] = None
                      ) -> optax.GradientTransformation:
    if warmup_steps or total_steps:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps or 1,
            total_steps or (warmup_steps or 1) * 10)
    else:
        schedule = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


# The TPU compiler's async-collective / latency-hiding knobs: with
# these on, the per-leaf grad "buckets" the overlap path emits become
# independently schedulable async reduce-scatters that the latency-
# hiding scheduler hoists into the backward, instead of one fused
# blocking all-reduce after it. The TPU compiler lives inside libtpu,
# so they go in LIBTPU_INIT_ARGS, before backend init (train_lm
# --overlap sets them): in XLA_FLAGS the host-side parser aborts the
# process on them ("Unknown flag in XLA_FLAGS", my chip run, PR 21).
# Nothing but libtpu reads that variable, so a CPU run ignores it.
OVERLAP_LIBTPU_FLAGS: Tuple[str, ...] = (
    '--xla_tpu_enable_async_collective_fusion=true',
    '--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true',
    '--xla_tpu_enable_async_collective_fusion_multiple_steps=true',
    '--xla_tpu_overlap_compute_collective_tc=true',
    '--xla_enable_async_all_gather=true',
    '--xla_enable_async_collective_permute=true',
)


def _supports_fused(model: nn.Module, loss_fn: Callable) -> bool:
    """Can this (model, loss) pair ride the fused blockwise xent path?

    The model must expose `return_hidden` in its apply signature and
    the loss must be the stock next-token CE (or flagged `fused_ok`,
    e.g. mixtral's CE + aux-loss wrapper) — a custom logits-space loss
    needs the logits and stays on the naive path.
    """
    try:
        sig = inspect.signature(type(model).__call__)
    except (TypeError, ValueError):  # builtins / exotic callables
        return False
    if 'return_hidden' not in sig.parameters:
        return False
    return loss_fn is next_token_loss or bool(
        getattr(loss_fn, 'fused_ok', False))


class ShardedTrainer:
    """Builds sharded init/step functions for a flax LM over a mesh.

    `fused_xent` (None = auto) routes the loss through the blockwise
    LM-head cross-entropy (ops/fused_xent.py): the model returns final
    hidden states and the [B, S, V] logits tensor — the training
    memory high-water mark — is never materialized in either pass.
    Auto enables it whenever the model supports `return_hidden` and
    the loss is the stock CE; `False` forces the naive path.

    `zero1` shards the optimizer moments (ZeRO-1, Xu et al.
    arXiv:2004.13336) over the mesh's `data` axis on top of whatever
    fsdp/tensor layout the params already use: each data replica
    keeps 1/data of the Adam m/v state, GSPMD reduce-scatters the
    grads into the shards and all-gathers the updated params — the
    step math (and loss curve) is unchanged.

    `lora` (models/lora.py LoraSpec) turns the run into a LoRA
    finetune: the params pytree becomes `{'base': ..., 'lora': ...}`,
    the base half is frozen (stop_gradient in the loss + a zeroed
    optimizer partition with NO Adam moments allocated for it), and
    only the per-projection A/B factors train. Guard, checkpoint,
    multi-step, and ZeRO-1 paths see an ordinary params pytree and
    work unchanged; `train_lm --lora` saves the trained factors as a
    serving-ready adapter artifact.

    `guard` arms the self-supervising bad-step guard
    (robustness/train_guard.py): the train step takes an extra
    `ctl = [max_grad_norm, loss_scale]` array, flags the step bad ON
    DEVICE when the loss or global grad norm is non-finite or the
    norm exceeds `max_grad_norm`, and SKIPS the update by selecting
    the old params/opt_state — no host round-trip sits between a NaN
    and the optimizer. The step counter still advances (a skipped
    batch is consumed), aux becomes `(loss, grad_norm, bad)`, and
    `loss_scale` exists so a fault plan can poison one step's loss
    with NaN through the real isfinite path. Guarding implies grad-
    norm collection; the norm is computed ONCE and shared by the
    guard predicate and the metrics aux.
    """

    def __init__(self, model: nn.Module, mesh: Mesh,
                 tx: Optional[optax.GradientTransformation] = None,
                 rules=mesh_lib.DEFAULT_RULES,
                 loss_fn: Callable[[jax.Array, jax.Array],
                                   jax.Array] = next_token_loss,
                 fused_xent: Optional[bool] = None,
                 zero1: bool = False,
                 overlap: bool = False,
                 collect_grad_norm: bool = False,
                 guard: bool = False,
                 lora: Optional[lora_lib.LoraSpec] = None) -> None:
        self.model = model
        self.mesh = mesh
        self.tx = tx if tx is not None else default_optimizer()
        self.lora = lora
        if lora is not None:
            if not lora_lib.supports(model):
                raise ValueError(
                    f'{type(model).__name__} has no LoRA forward '
                    f'path; --lora supports the Llama family '
                    f'(models/lora.py)')
            # Freeze the base: its partition of the optimizer emits
            # zero updates and allocates NO moments (optax.masked
            # replaces frozen leaves with MaskedNode at init), so
            # checkpoints and ZeRO-1 sharding cover only what trains.
            base_tx = self.tx

            def _labels(params):
                return {'base': jax.tree.map(lambda _: 'base',
                                             params['base']),
                        'lora': jax.tree.map(lambda _: 'lora',
                                             params['lora'])}

            self.tx = optax.multi_transform(
                {'lora': base_tx, 'base': optax.set_to_zero()},
                _labels)
        self.rules = rules
        self.loss_fn = loss_fn
        self.zero1 = zero1
        if overlap and not zero1:
            raise ValueError(
                'overlap=True buckets the grad reduce-scatter onto '
                'the ZeRO-1 moment layout; it needs zero1=True')
        # Collective/compute overlap (arXiv:2004.13336 §4): pin each
        # grad LEAF to the ZeRO-1 data-sharded layout right where the
        # backward produces it, so XLA emits one independent
        # reduce-scatter per stacked-layer leaf (schedulable into the
        # backward under OVERLAP_LIBTPU_FLAGS) instead of one fused
        # all-reduce after the full backward.
        self.overlap = overlap
        self.guard = guard
        # Step metrics (`train_lm --metrics-file`): the step returns
        # (loss, grad_norm) instead of a bare loss. The norm is
        # computed from grads already in registers — free next to the
        # step itself. The guard needs it unconditionally.
        self.collect_grad_norm = collect_grad_norm or guard
        supported = _supports_fused(model, loss_fn)
        if fused_xent and not supported:
            raise ValueError(
                f'fused_xent=True but {type(model).__name__} has no '
                f'return_hidden apply path or the loss_fn is not '
                f'fused-compatible')
        self.fused_xent = supported if fused_xent is None else bool(
            fused_xent)
        self.batch_sharding = mesh_lib.batch_sharding(mesh)
        self._state_sharding: Optional[Any] = None
        self._grad_sharding: Optional[Any] = None

    def _full_params(self, rng: jax.Array, example_tokens: jax.Array
                     ) -> Any:
        """The trainable params pytree: the model's init, wrapped as
        {'base', 'lora'} when LoRA-finetuning (fresh factors: a ~
        N(0, .02), b = 0, so step 0 is exactly the base model)."""
        params = self.model.init(rng, example_tokens)['params']
        if self.lora is not None:
            params = {
                'base': params,
                'lora': lora_lib.init_lora_params(
                    jax.random.fold_in(rng, 7), self.model.config,
                    self.lora),
            }
        return params

    # -- sharding inference -------------------------------------------------
    def state_sharding(self, example_tokens: jax.Array) -> Any:
        if self._state_sharding is None:
            abstract = jax.eval_shape(
                lambda: TrainState.create(
                    self._full_params(jax.random.PRNGKey(0),
                                      example_tokens),
                    self.tx))
            specs = nn.get_partition_spec(abstract)
            sharding = nn.logical_to_mesh_sharding(
                specs, self.mesh, self.rules)
            if self.zero1:
                shapes = jax.tree.map(
                    lambda x: x.unbox() if isinstance(x, nn.Partitioned)
                    else x,
                    abstract.opt_state,
                    is_leaf=lambda x: isinstance(x, nn.Partitioned))
                sharding = sharding.replace(
                    opt_state=self._zero1_opt_sharding(
                        sharding.opt_state, shapes))
                # The grad "buckets" for collective/compute overlap:
                # the params tree mapped through the same data-axis
                # layering the moments got — each grad leaf lands
                # directly in the layout its moment shard consumes.
                param_shapes = jax.tree.map(
                    lambda x: x.unbox() if isinstance(x, nn.Partitioned)
                    else x,
                    abstract.params,
                    is_leaf=lambda x: isinstance(x, nn.Partitioned))
                self._grad_sharding = self._zero1_opt_sharding(
                    sharding.params, param_shapes)
            self._state_sharding = sharding
        return self._state_sharding

    def _zero1_opt_sharding(self, opt_sharding: Any, opt_shapes: Any
                            ) -> Any:
        """ZeRO-1: layer the `data` mesh axis onto each optimizer-state
        leaf's sharding. Picks the first dim whose size the combined
        (existing axes x data) factor divides; leaves that fit nowhere
        (scalars like Adam's `count`, odd-sized vectors) stay as-is —
        they are noise next to the m/v moments."""
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        data = sizes.get('data', 1)
        if data <= 1:
            return opt_sharding

        def _axes(entry):
            if entry is None:
                return ()
            return entry if isinstance(entry, tuple) else (entry,)

        def shard_leaf(s, shape_leaf):
            shape = getattr(shape_leaf, 'shape', ())
            if not isinstance(s, NamedSharding) or len(shape) == 0:
                return s
            spec = list(s.spec) + [None] * (len(shape) - len(s.spec))
            if any('data' in _axes(e) for e in spec):
                return s
            for dim, entry in enumerate(spec):
                axes = _axes(entry)
                cur = 1
                for a in axes:
                    cur *= sizes.get(a, 1)
                if shape[dim] % (cur * data) == 0:
                    spec[dim] = (*axes, 'data') if axes else 'data'
                    return NamedSharding(self.mesh, P(*spec))
            return s

        return jax.tree.map(shard_leaf, opt_sharding, opt_shapes)

    # -- init ---------------------------------------------------------------
    def init(self, rng: jax.Array, example_tokens: jax.Array) -> TrainState:
        sharding = self.state_sharding(example_tokens)

        def _init() -> TrainState:
            params = self._full_params(rng, example_tokens)
            params = jax.tree.map(
                lambda x: x.unbox() if isinstance(x, nn.Partitioned) else x,
                params,
                is_leaf=lambda x: isinstance(x, nn.Partitioned))
            return TrainState.create(params, self.tx)

        from skypilot_tpu.parallel import context as cp_context
        with self.mesh, cp_context.context_parallel(self.mesh):
            with nn.logical_axis_rules(self.rules):
                return jax.jit(_init, out_shardings=sharding)()

    # -- step ---------------------------------------------------------------
    def _compute_loss(self, params: Any, tokens: jax.Array) -> jax.Array:
        extra = {}
        model_params = params
        if self.lora is not None:
            # Frozen base: stop_gradient prunes the base backward
            # pass entirely — grads flow only into the A/B factors
            # applied inside the forward (models/lora.py).
            model_params = jax.lax.stop_gradient(params['base'])
            extra = {'lora': lora_lib.as_model_lora(params['lora'],
                                                    self.lora.scale)}
        if self.fused_xent:
            out = self.model.apply({'params': model_params}, tokens,
                                   return_hidden=True, **extra)
            aux = None
            if isinstance(out, (tuple, list)):
                out, aux = out
            head, vocab_in_rows = fused_xent.find_lm_head(model_params)
            loss = fused_xent.fused_next_token_loss(
                out, head, tokens, vocab_in_rows=vocab_in_rows)
            return loss if aux is None else loss + aux
        outputs = self.model.apply({'params': model_params}, tokens,
                                   **extra)
        return self.loss_fn(outputs, tokens)

    def _step_body(self, state: TrainState, tokens: jax.Array,
                   ctl: Optional[jax.Array] = None
                   ) -> Tuple[TrainState, Any]:
        if ctl is None:
            loss, grads = jax.value_and_grad(self._compute_loss)(
                state.params, tokens)
        else:
            # Guarded step: ctl = [max_grad_norm, loss_scale]. The
            # scale rides INSIDE value_and_grad so an injected NaN
            # poisons loss AND grads — exactly the bf16-overflow
            # shape the isfinite predicate exists for.
            loss, grads = jax.value_and_grad(
                lambda p: self._compute_loss(p, tokens) * ctl[1])(
                    state.params)
        if self.overlap and self._grad_sharding is not None:
            # One constraint PER LEAF: each reduce-scatter becomes an
            # independent collective XLA's latency-hiding scheduler
            # can issue as soon as the backward finishes that leaf,
            # instead of one fused tuple-all-reduce at the join.
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s)
                if isinstance(s, NamedSharding) else g,
                grads, self._grad_sharding)
        gnorm = (optax.global_norm(grads) if self.collect_grad_norm
                 else None)
        with jax.named_scope('optimizer'):
            updates, opt_state = self.tx.update(
                grads, state.opt_state, state.params)
        if self.zero1 and self._state_sharding is not None:
            # Pin the moment update to the ZeRO-1 layout *inside* the
            # step (the jit out_shardings only constrain the final
            # carry — this keeps every lax.scan iteration of the
            # multi-step path sharded too, so GSPMD reduce-scatters
            # grads into the moment shards instead of materializing
            # replicated Adam state between inner steps).
            opt_state = jax.lax.with_sharding_constraint(
                opt_state, self._state_sharding.opt_state)
        with jax.named_scope('optimizer'):
            params = optax.apply_updates(state.params, updates)
        if ctl is None:
            aux = loss if gnorm is None else (loss, gnorm)
            return state.replace(step=state.step + 1, params=params,
                                 opt_state=opt_state), aux
        # Bad step — non-finite loss/norm, or a norm spike past the
        # host-supplied ceiling: select the OLD params and opt_state
        # (the update never happens), but still consume the step.
        bad = jnp.logical_or(
            jnp.logical_or(~jnp.isfinite(loss), ~jnp.isfinite(gnorm)),
            gnorm > ctl[0])
        params = jax.tree.map(
            lambda new, old: jnp.where(bad, old, new),
            params, state.params)
        opt_state = jax.tree.map(
            lambda new, old: jnp.where(bad, old, new),
            opt_state, state.opt_state)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), (loss, gnorm, bad)

    def _wrap(self, step: Callable) -> Callable:
        def wrapped(state, tokens, *extra):
            from skypilot_tpu.parallel import context as cp_context
            with self.mesh, cp_context.context_parallel(self.mesh):
                with nn.logical_axis_rules(self.rules):
                    return step(state, tokens, *extra)

        wrapped.lower = lambda s, t: step.lower(s, t)  # type: ignore
        return wrapped

    def make_train_step(self, example_tokens: jax.Array,
                        donate: bool = True) -> Callable:
        """The per-step train fn. Unguarded: `(state, tokens) ->
        (state, aux)`. With `guard=True`: `(state, tokens,
        max_grad_norm, loss_scale) -> (state, (loss, gnorm, bad))` —
        the two guard scalars ride one replicated f32 array."""
        sharding = self.state_sharding(example_tokens)
        scalar = NamedSharding(self.mesh, P())
        if not self.guard:
            step = jax.jit(
                self._step_body,
                in_shardings=(sharding, self.batch_sharding),
                out_shardings=(sharding, scalar),
                donate_argnums=(0,) if donate else ())
            return self._wrap(step)
        step = jax.jit(
            self._step_body,
            in_shardings=(sharding, self.batch_sharding, scalar),
            out_shardings=(sharding, scalar),
            donate_argnums=(0,) if donate else ())
        wrapped = self._wrap(step)

        def guarded(state, tokens, max_grad_norm=float('inf'),
                    loss_scale=1.0):
            ctl = jnp.asarray([max_grad_norm, loss_scale],
                              dtype=jnp.float32)
            return wrapped(state, tokens, ctl)

        return guarded

    def make_multi_step(self, example_tokens: jax.Array,
                        inner_steps: int,
                        donate: bool = True) -> Callable:
        """`inner_steps` optimizer steps inside ONE jitted call.

        `lax.scan` keeps the whole inner loop on-device: one dispatch,
        one executable, N steps — amortizing host->device dispatch
        latency. Takes tokens stacked
        [inner_steps, B, S]; returns (state, losses[inner_steps]).
        """
        sharding = self.state_sharding(example_tokens)
        stacked = NamedSharding(
            self.mesh, P(None, *self.batch_sharding.spec))

        def _multi(state: TrainState, tokens_stack: jax.Array
                   ) -> Tuple[TrainState, jax.Array]:
            assert tokens_stack.shape[0] == inner_steps, (
                f'tokens stack has {tokens_stack.shape[0]} steps, '
                f'trainer was built for {inner_steps}')
            return jax.lax.scan(self._step_body, state, tokens_stack)

        step = jax.jit(
            _multi,
            in_shardings=(sharding, stacked),
            out_shardings=(sharding, NamedSharding(self.mesh, P())),
            donate_argnums=(0,) if donate else ())
        return self._wrap(step)

    def make_eval_step(self, example_tokens: jax.Array) -> Callable:
        sharding = self.state_sharding(example_tokens)

        def _eval(state: TrainState, tokens: jax.Array) -> jax.Array:
            return self._compute_loss(state.params, tokens)

        step = jax.jit(_eval,
                       in_shardings=(sharding, self.batch_sharding),
                       out_shardings=NamedSharding(self.mesh, P()))

        def wrapped(state, tokens):
            from skypilot_tpu.parallel import context as cp_context
            with self.mesh, cp_context.context_parallel(self.mesh):
                with nn.logical_axis_rules(self.rules):
                    return step(state, tokens)

        return wrapped


def shard_batch(tokens: jax.Array, mesh: Mesh) -> jax.Array:
    return jax.device_put(tokens, mesh_lib.batch_sharding(mesh))


def shard_batch_stack(tokens_stack: jax.Array, mesh: Mesh) -> jax.Array:
    """Places a [inner_steps, B, S] stack for `make_multi_step`: the
    leading scan axis replicated, each [B, S] slice batch-sharded."""
    spec = mesh_lib.batch_sharding(mesh).spec
    return jax.device_put(tokens_stack,
                          NamedSharding(mesh, P(None, *spec)))
