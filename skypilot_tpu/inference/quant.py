"""Quantized weight storage for serving: per-channel int8 projections.

Decode throughput on TPU is bounded by HBM bytes streamed per token —
weights first, KV pages second. This module halves the weight half:
the big projection matrices (wq/wk/wv/wo, w_gate/w_up/w_down) are
stored as int8 with one f32 scale per OUTPUT channel (symmetric
absmax over the input dim), and dequantized on read INSIDE the jitted
serving functions, so every matmul still runs in bf16/f32 off
on-chip dequantized operands. Embeddings, the LM head, norms, and
biases stay in their serving dtype — they are either accuracy-
critical (norms) or shared with sampling-path numerics (head).

Two pieces:

  - `quantize_params` rewrites the param pytree: a targeted module's
    {'kernel': W} becomes {'kernel_q': int8, 'kernel_scale': f32[out]}
    (bias untouched). Host-side numpy — runs once at server startup.
  - `QuantizedModel` wraps the flax module transparently: `apply`
    dequantizes a quantized `params` tree at trace time (one
    `int8 -> f32 * scale` op per projection, fused by XLA into the
    consumer matmul) and delegates everything else. Every serving
    call site — the continuous engine's jitted fns, the one-shot
    generate buckets, the /v1/completions scorer — works unchanged,
    and LoRA deltas apply in f32 ON TOP of the dequantized base
    (models/lora.py operates on projection outputs, not kernels).

Tensor parallelism composes: `shard_quantized_for_serving` places
kernel_q with the base kernel's NamedSharding and each scale vector
with its output-channel axis (the kernel's axis-1 mesh axis), per the
parallel/serving.py rules.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

#: Projection modules quantized by default: the Llama-family big
#: matmuls (the GQA attention block + SwiGLU MLP). Matches
#: models/lora.py ALL_TARGETS — LoRA and weight quantization cover
#: the same surfaces.
WEIGHT_TARGETS: Tuple[str, ...] = ('wq', 'wk', 'wv', 'wo',
                                   'w_gate', 'w_up', 'w_down')
QUANT_KEY = 'kernel_q'
SCALE_KEY = 'kernel_scale'


def quantize_params(params: Dict[str, Any],
                    targets: Tuple[str, ...] = WEIGHT_TARGETS
                    ) -> Dict[str, Any]:
    """Per-output-channel symmetric int8 quantization of the targeted
    projection kernels; every other leaf passes through untouched
    (as host numpy). scale[j] = max|W[:, j]| / 127; W ~= q * scale."""
    import jax

    def walk(node, name):
        if isinstance(node, dict):
            kernel = node.get('kernel') if name in targets else None
            if kernel is not None and getattr(kernel, 'ndim', 0) == 2:
                w = np.asarray(jax.device_get(kernel), np.float32)
                amax = np.abs(w).max(axis=0)
                scale = (amax / 127.0).astype(np.float32)
                safe = np.where(scale > 0, scale, 1.0)
                q = np.clip(np.rint(w / safe), -127,
                            127).astype(np.int8)
                out = {QUANT_KEY: q, SCALE_KEY: scale}
                for key, val in node.items():
                    if key != 'kernel':
                        out[key] = np.asarray(jax.device_get(val))
                return out
            return {key: walk(val, key) for key, val in node.items()}
        return node

    return walk(params, '')


def is_quantized(params: Any) -> bool:
    """True when the tree holds at least one quantized kernel."""
    if isinstance(params, dict):
        if QUANT_KEY in params:
            return True
        return any(is_quantized(v) for v in params.values())
    return False


def dequantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Rebuild a dense param tree in-graph: quantized kernels become
    f32 `int8 * scale` products (the consumer Dense casts to its
    compute dtype). Called at trace time inside every jitted serving
    fn via QuantizedModel.apply — the int8 tensors are what streams
    from HBM; the dequant fuses into the matmul."""
    import jax.numpy as jnp

    def walk(node):
        if isinstance(node, dict):
            if QUANT_KEY in node:
                out = {key: val for key, val in node.items()
                       if key not in (QUANT_KEY, SCALE_KEY)}
                out['kernel'] = (node[QUANT_KEY].astype(jnp.float32) *
                                 node[SCALE_KEY])
                return out
            return {key: walk(val) for key, val in node.items()}
        return node

    return walk(params)


def weight_num_bytes(params: Any) -> int:
    """Device bytes of a (possibly quantized) param tree — the
    skypilot_serving_weight_bytes gauge."""
    import jax
    import jax.numpy as jnp
    return int(sum(
        leaf.size * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(params)))


class QuantizedModel:
    """Transparent dequant-on-read wrapper around a flax model.

    `apply` swaps a quantized `params` collection for its in-graph
    dequantized form before delegating; `init`, `config`, and every
    other attribute delegate to the base model, so the continuous
    engine, the one-shot buckets, the scorer, and the adapter
    registry all serve a quantized model without special cases
    (models/lora.py `supports` unwraps via `base_model`)."""

    def __init__(self, model) -> None:
        self.base_model = model

    @property
    def config(self):
        return self.base_model.config

    def apply(self, variables, *args, **kwargs):
        if isinstance(variables, dict) and \
                is_quantized(variables.get('params')):
            variables = dict(variables)
            variables['params'] = dequantize_params(
                variables['params'])
        return self.base_model.apply(variables, *args, **kwargs)

    def init(self, *args, **kwargs):
        return self.base_model.init(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.base_model, name)


def kv_page_bytes(cfg, kv_dtype: str, shard_ways: int = 1,
                  stages: int = 1) -> int:
    """Device bytes ONE physical KV page costs across all layers ON
    ONE CHIP (K + V values, plus scale slots for int8) — the unit the
    --kv-pool-bytes knob divides by, so a byte budget maps to the
    same HBM spend for either storage format.

    `shard_ways` is how many ways the pool's kv-heads axis shards
    over the mesh (parallel/serving.py kv_shard_ways): each chip then
    stores 1/shard_ways of the VALUE bytes but the FULL scale rows
    (per-token scales replicate — every head shard quantizes against
    the same scale), so an N-way pool's per-chip page is cheaper and
    the same per-chip budget buys ~N x the pages.

    `stages` is the pipeline-stage count (PR 19): each stage's chips
    hold pages for only that stage's layers — the WIDEST stage
    (ceil(num_layers / stages), stage_layer_ranges front-loads the
    remainder) bounds the per-chip cost, so an S-stage T-way mesh
    holds ~S·T x the pages at the same per-chip budget."""
    import jax.numpy as jnp
    # The token's row comes from the model (ops/paged_attention
    # .PageLayout): K and V heads, or a latent row and an indexer key.
    layout = cfg.page_layout()
    per_layer = layout.row_values * cfg.kv_page_size
    item = jnp.dtype(cfg.dtype).itemsize
    heads = layout.arrays[0].heads
    if heads % shard_ways:
        raise ValueError(
            f'shard_ways={shard_ways} does not divide num_kv_heads='
            f'{heads} (the GQA remainder rule replicates '
            f'instead — pass shard_ways=1)')
    if kv_dtype == 'int8' and layout.kind != 'kv':
        raise ValueError(
            f'--kv-dtype int8 stores K/V pages with per-token scales; '
            f'a {layout.kind!r} page layout has no int8 form yet '
            f'(ROADMAP R-M1: int8 latent pages)')
    if stages < 1 or stages > cfg.num_layers:
        raise ValueError(
            f'stages={stages} must be in [1, num_layers='
            f'{cfg.num_layers}]')
    if kv_dtype == 'int8':
        value_bytes = per_layer // shard_ways
        scale_bytes = len(layout.arrays) * cfg.kv_page_size * 4
    else:
        value_bytes = (layout.row_bytes(item) * cfg.kv_page_size
                       // shard_ways)
        scale_bytes = 0
    # The layers that keep a row (a hybrid model: its attention layers).
    pool_layers = layout.layers or cfg.num_layers
    stage_layers = -(-pool_layers // stages)  # ceil: widest stage
    return stage_layers * (value_bytes + scale_bytes)


def pool_pages_for_bytes(cfg, kv_dtype: str, pool_bytes: int,
                         shard_ways: int = 1, stages: int = 1) -> int:
    """Physical pages a PER-CHIP byte budget buys under `kv_dtype` —
    how serve_lm --kv-pool-bytes sizes kv_total_pages (int8 fits ~2x
    the pages of bf16 in the same bytes; a pool head-sharded
    `shard_ways` ways fits ~shard_ways more again at the same
    per-chip HBM, and splitting layers over `stages` pipeline stages
    multiplies by ~stages on top — each stage stores only its own
    layers' pages)."""
    pages = pool_bytes // kv_page_bytes(cfg, kv_dtype, shard_ways,
                                        stages)
    if pages < 2:
        raise ValueError(
            f'--kv-pool-bytes {pool_bytes} buys {pages} pages '
            f'({kv_page_bytes(cfg, kv_dtype, shard_ways, stages)} '
            f'bytes/page across layers, kv_dtype={kv_dtype}); need '
            f'>= 2 (page 0 is the trash page)')
    return int(pages)


def shard_quantized_for_serving(model, qparams: Dict[str, Any],
                                mesh, rules=None,
                                dtype: Optional[Any] = None
                                ) -> Dict[str, Any]:
    """Tensor-parallel placement of a quantized param tree: kernel_q
    takes the base kernel's NamedSharding, kernel_scale shards over
    the kernel's OUTPUT-channel mesh axis (scales live with their
    channel), everything else places per the base rules — shard-only
    transfers, like shard_params_for_serving. `dtype` casts
    non-quantized leaves per leaf immediately before placement."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel.serving import serving_param_shardings
    if rules is None:
        rules = mesh_lib.DEFAULT_RULES
    base = getattr(model, 'base_model', model)
    shardings = serving_param_shardings(base, mesh, rules)

    def place(leaf, sharding, cast):
        if cast and dtype is not None:
            leaf = np.asarray(leaf).astype(dtype)
        return jax.device_put(leaf, sharding)

    def walk(qnode, snode):
        if isinstance(qnode, dict) and QUANT_KEY in qnode:
            kernel_sh = snode['kernel']
            spec = tuple(kernel_sh.spec)
            out_axis = spec[1] if len(spec) > 1 else None
            scale_sh = NamedSharding(mesh, PartitionSpec(out_axis))
            out = {QUANT_KEY: place(qnode[QUANT_KEY], kernel_sh,
                                    cast=False),
                   SCALE_KEY: place(qnode[SCALE_KEY], scale_sh,
                                    cast=False)}
            for key, val in qnode.items():
                if key in (QUANT_KEY, SCALE_KEY):
                    continue
                out[key] = place(val, snode[key], cast=True)
            return out
        if isinstance(qnode, dict):
            return {key: walk(val, snode[key])
                    for key, val in qnode.items()}
        return place(qnode, snode, cast=True)

    return walk(qparams, shardings)
