"""Multi-device serving: tensor-parallel parameter placement.

Serving was single-device (ADVICE r3: an 8B checkpoint needs a
v5p-class chip). This lifts that: place the model's params with the
same logical→mesh rules training uses (wq/wk/wv/mlp sharded over the
`tensor` axis), and XLA GSPMD *propagates* the sharding through every
jitted serving function — prefill, decode, the continuous-batching
engine's fns — inserting the TP collectives (all-reduce after wo /
w_down) automatically. No serving code changes and no thread-local
mesh/rules contexts are needed: propagation from the input params is
sufficient (the models' `with_logical_constraint` hints are no-ops
without an active rules context, which is fine — constraints are
hints, placement comes from the params).

    mesh = make_mesh(MeshConfig(tensor=8))
    params = shard_params_for_serving(model, params, mesh)
    engine = ContinuousBatchingEngine(model, params, ...)

The KV cache is placed EXPLICITLY (PR 15): `serving_cache_shardings`
pins the paged pool's kv-heads axis over `tensor` (GQA remainder
rule: shard only when the head count divides evenly, else replicate)
and the engine declares those shardings on every jitted dispatch's
donated cache output — zero per-step resharding of the pool, which
`pool_collective_lines` lets tests assert from the compiled HLO;
`pool_copy_lines` is its single-chip sibling (no whole-pool copy
around the KV write).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from skypilot_tpu.ops.paged_attention import (POOL_LEAF_NAMES,
                                               SLOT_LEAF_NAMES)
from skypilot_tpu.parallel import mesh as mesh_lib


def serving_param_shardings(model, mesh: Mesh,
                            rules=mesh_lib.DEFAULT_RULES) -> Any:
    """NamedShardings for the model's params from its logical axis
    annotations (the training rules table — TP shards heads/mlp/vocab
    over `tensor`)."""
    import flax.linen as nn
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 8), jnp.int32)))['params']
    specs = nn.get_partition_spec(abstract)
    return nn.logical_to_mesh_sharding(specs, mesh, rules)


def shard_params_for_serving(model, params: Any, mesh: Mesh,
                             rules=mesh_lib.DEFAULT_RULES,
                             dtype=None) -> Any:
    """Place `params` (host numpy or device arrays) onto the mesh with
    the model's logical shardings; returns the sharded tree.

    `device_put` is called on the HOST array directly — with a
    NamedSharding it transfers only each device's shard, never a full
    single-device copy (the whole point for bigger-than-one-chip
    models). `dtype` casts per leaf immediately before placement, so
    the host-side transient is one leaf, not a second full tree."""
    import numpy as np
    shardings = serving_param_shardings(model, mesh, rules)

    def _place(w, s):
        if dtype is not None:
            w = np.asarray(w).astype(dtype)
        return jax.device_put(w, s)

    return jax.tree.map(_place, params, shardings)


# -- KV cache placement (PR 15) ---------------------------------------------
#: Cache-collection leaf names with a kv-heads axis. Paged pool
#: values are [num_kv_heads, total_pages, page_size, head_dim]
#: (ops/paged_attention.py); dense per-slot rows are
#: [slots, max_seq, num_kv_heads, head_dim] (models/llama.py).
#: Every pool array a model's page layout can name (K and V pages,
#: MLA's latent rows and indexer keys; ops/paged_attention.PageLayout):
#: all [heads, pages, page, width]. A latent array has one head for
#: all query heads, so the remainder rule below replicates it.
_PAGED_VALUE_LEAVES = POOL_LEAF_NAMES
#: Parallel int8 scale pages [total_pages, page_size]: ONE f32 scale
#: per token slot, shared by every kv head — always replicated (a
#: head-sharded device still needs the whole scale row to
#: quantize/dequantize its own heads).
_PAGED_SCALE_LEAVES = ('k_scales', 'v_scales')
#: State by slot ([slots, *a slot's row]; ops/paged_attention
#: .SlotArray): replicated (a tensor mesh is refused for such a model),
#: and held to the same two guards as the pool, by its whole shape.
_SLOT_LEAVES = SLOT_LEAF_NAMES
_DENSE_LEAVES = ('cached_key', 'cached_value')


def kv_shard_ways(num_kv_heads: int, tensor_size: int) -> int:
    """How many ways the KV-heads axis shards over a `tensor` axis of
    `tensor_size` devices. The GQA remainder rule: a NamedSharding
    splits an axis all-or-nothing, so the pool shards as far as heads
    allow — `tensor_size` ways when the head count divides evenly,
    else it REPLICATES (e.g. 8 kv heads over tensor=2 shard 2-way;
    2 kv heads over tensor=4 replicate; attention q-heads still shard
    because the weights do — only the KV pool pays the remainder)."""
    if tensor_size > 1 and num_kv_heads > 0 and \
            num_kv_heads % tensor_size == 0:
        return int(tensor_size)
    return 1


def _leaf_name(path) -> str:
    for entry in reversed(path):
        key = getattr(entry, 'key', None)
        if isinstance(key, str):
            return key
    return ''


def serving_cache_shardings(cache: Any, mesh: Mesh) -> Any:
    """NamedShardings for an engine's cache collection: paged pool
    values shard their kv-heads axis (axis 0) over `tensor`, dense
    rows shard theirs (axis 2), scale pages and every other leaf
    (MLA latents, paged or dense, which have one head for all query
    heads; bookkeeping scalars and counters) replicate. The engine pins
    these on the donated cache of every jitted dispatch, so an
    N-chip mesh stores 1/N of each value page per chip and never
    reshards the pool between steps."""
    tensor = int(mesh.shape.get('tensor', 1))
    replicated = NamedSharding(mesh, PartitionSpec())

    def spec_for(path, leaf):
        name = _leaf_name(path)
        if name in _PAGED_VALUE_LEAVES and leaf.ndim == 4 and \
                kv_shard_ways(leaf.shape[0], tensor) > 1:
            return NamedSharding(mesh, PartitionSpec('tensor'))
        if name in _DENSE_LEAVES and leaf.ndim == 4 and \
                kv_shard_ways(leaf.shape[2], tensor) > 1:
            return NamedSharding(mesh,
                                 PartitionSpec(None, None, 'tensor'))
        return replicated

    return jax.tree_util.tree_map_with_path(spec_for, cache)


# -- Pipeline stages (PR 19) ------------------------------------------------
def stage_layer_ranges(num_layers: int,
                       stages: int) -> List[Tuple[int, int]]:
    """Contiguous [lo, hi) layer ranges per stage. Earlier stages take
    the remainder layers (stage 0 also owns the embedding table, but
    the KV pool only materializes transformer layers, so front-loading
    keeps the per-stage POOL split as even as the layer count
    allows)."""
    if stages < 1:
        raise ValueError(f'stages must be >= 1, got {stages}')
    if stages > num_layers:
        raise ValueError(
            f'cannot split {num_layers} layers over {stages} stages '
            f'(at least one layer per stage)')
    base, rem = divmod(num_layers, stages)
    ranges: List[Tuple[int, int]] = []
    lo = 0
    for s in range(stages):
        hi = lo + base + (1 if s < rem else 0)
        ranges.append((lo, hi))
        lo = hi
    return ranges


def stage_submeshes(mesh: Mesh) -> List[Mesh]:
    """One tensor-only `Mesh` per stage row of a `(stage, tensor)`
    mesh. Every existing TP machine — `serving_param_shardings`,
    `serving_cache_shardings`, `kv_shard_ways`,
    `pool_collective_lines` — applies per stage on its submesh
    unchanged: within a stage the layout IS the PR 15 tensor-parallel
    layout, and the only cross-stage traffic is the activation
    handoff between stages (host-driven `device_put`, never a pool
    collective)."""
    stages = int(mesh.shape.get('stage', 1))
    tensor = int(mesh.shape.get('tensor', 1))
    devices = np.asarray(mesh.devices).reshape(stages, tensor)
    # Full six-axis meshes (size-1 everywhere but tensor) so the
    # training rules table resolves every logical axis on a submesh
    # exactly like it does on a plain --tensor mesh.
    return [mesh_lib.make_mesh(mesh_lib.MeshConfig(tensor=tensor),
                               devices=list(devices[s]))
            for s in range(stages)]


def build_staged_serving(model, params: Any, mesh: Mesh,
                         rules=mesh_lib.DEFAULT_RULES,
                         dtype=None) -> Tuple[List[Any], List[Any],
                                              List[Mesh],
                                              List[Tuple[int, int]]]:
    """Split a full Llama param tree into per-stage trees and place
    each on its stage's tensor submesh.

    Stage modules use ABSOLUTE layer names (`models/llama.py
    LlamaStage`), so the split is a top-level dict partition:
    `layer_i` goes to the stage whose [lo, hi) holds i, `tok_embed`
    to stage 0, `final_norm`/`lm_head` to the last stage. Shardings
    come from the FULL model's logical annotations evaluated on each
    submesh — per-stage placement is therefore leaf-for-leaf
    identical to what single-stage TP serving would pin, which is
    what keeps staged outputs bit-identical.

    Returns (stage_models, stage_params, submeshes, layer_ranges).
    """
    from skypilot_tpu.models import llama as llama_lib
    base = getattr(model, 'base_model', model)
    if not isinstance(base, llama_lib.Llama):
        raise ValueError(
            f'staged serving supports the Llama family; '
            f'{type(base).__name__} has no stage split')
    cfg = model.config
    stages = int(mesh.shape.get('stage', 1))
    ranges = stage_layer_ranges(cfg.num_layers, stages)
    submeshes = stage_submeshes(mesh)
    stage_models: List[Any] = []
    stage_params: List[Any] = []
    for s, (lo, hi) in enumerate(ranges):
        first, last = s == 0, s == stages - 1
        stage_model = llama_lib.LlamaStage(
            cfg, lo=lo, hi=hi, first=first, last=last)
        keys = {f'layer_{i}' for i in range(lo, hi)}
        if first:
            keys.add('tok_embed')
        if last:
            keys |= {'final_norm', 'lm_head'}
        missing = keys - set(params)
        if missing:
            raise ValueError(
                f'stage {s} needs params {sorted(missing)} not in '
                f'the provided tree (keys: {sorted(params)[:8]}...)')
        shardings = serving_param_shardings(model, submeshes[s],
                                            rules)
        sub = {k: params[k] for k in keys}
        sub_shardings = {k: shardings[k] for k in keys}

        def _place(w, sh):
            if dtype is not None:
                w = np.asarray(w).astype(dtype)
            return jax.device_put(w, sh)

        stage_models.append(stage_model)
        stage_params.append(jax.tree.map(_place, sub, sub_shardings))
    return stage_models, stage_params, submeshes, ranges


def pool_collective_lines(compiled: Any, cache: Any,
                          mesh: Mesh) -> List[str]:
    """HLO lines of a compiled serving module where a resharding
    collective (all-gather / all-to-all) touches a POOL-SHAPED
    operand — the zero-resharding guard for the sharded KV cache.

    `cache` supplies the KV leaves' global shapes; the match set
    holds their element counts at every way the mesh could split
    them, so both a gather OF a shard and a gather INTO the full
    pool trip it. TP's legitimate collectives (the all-reduce after
    wo/w_down, logit gathers) have activation-sized operands and
    pass. Returns the offending lines (empty = guard green)."""
    # Candidate split factors: 1 (full pool), one mesh axis (a
    # shard — what an all-gather consumes), and products of two
    # (the chunk an all-to-all splits a shard into).
    axes = [int(v) for v in mesh.shape.values()]
    ways = {1}
    for a in axes + axes:
        ways |= {w * a for w in ways}
    kv_names = (_PAGED_VALUE_LEAVES + _PAGED_SCALE_LEAVES +
                _DENSE_LEAVES + _SLOT_LEAVES)
    sizes = set()
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    for path, leaf in flat:
        if _leaf_name(path) not in kv_names:
            continue
        size = int(leaf.size)
        for w in ways:
            if size % w == 0:
                sizes.add(size // w)
    sizes.discard(0)
    text = compiled.as_text() if hasattr(compiled, 'as_text') \
        else str(compiled)
    hits = []
    for line in text.splitlines():
        # The collective APPLIED on this line, not one named among
        # its operands (`fusion(%all-gather.5, ...)` writing a few
        # gathered rows into the pool is the in-place KV write).
        if not re.search(r' all-(gather|to-all)(-start)?\(', line):
            continue
        for m in re.finditer(r'\[([0-9,]+)\]', line):
            n = 1
            for d in m.group(1).split(','):
                n *= int(d)
            if n in sizes:
                hits.append(line.strip())
                break
    return hits


def pool_copy_lines(compiled: Any, cache: Any) -> List[str]:
    """HLO lines of a compiled serving module where a `copy` produces
    a POOL-SHAPED array — the in-place-write guard for the page pool.

    A donated pool that is written in its own layout never shows one.
    The scatter form of the KV write did, twice per pool array per
    program (a layout round trip around every scatter, 168 MB each at
    a 5 GiB pool of 16 layers), which donation could not remove.
    `cache` supplies the k_pages / v_pages shapes (arrays or
    ShapeDtypeStructs); a shard of one along its leading kv-heads
    axis counts as pool-shaped too, and so does a whole array of
    state by slot (`ssm_state`, `conv_state`). Returns the offending
    lines (empty = guard green)."""
    shapes, whole = set(), set()
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    for path, leaf in flat:
        if _leaf_name(path) in _PAGED_VALUE_LEAVES and len(leaf.shape) == 4:
            shapes.add(tuple(leaf.shape))
        elif _leaf_name(path) in _SLOT_LEAVES:
            whole.add(tuple(leaf.shape))
    text = compiled.as_text() if hasattr(compiled, 'as_text') \
        else str(compiled)
    hits = []
    for line in text.splitlines():
        m = re.search(r'= \w+\[([0-9,]+)\]\S* copy\(', line)
        if m is None:
            continue
        dims = tuple(int(d) for d in m.group(1).split(','))
        if dims in whole or any(
                len(dims) == 4 and dims[1:] == shape[1:] and
                shape[0] % dims[0] == 0 for shape in shapes):
            hits.append(line.strip())
    return hits
