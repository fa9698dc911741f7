"""Pipeline parallelism (parallel/pipeline.py): the GPipe schedule
inside shard_map must be BIT-FAITHFUL to the sequential model —
same loss, same gradients — and train end-to-end on a stage x data
mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from skypilot_tpu.models.gpt import GPT, GPTConfig
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel.pipeline import (PipelinedGPT,
                                            stack_layer_params,
                                            unstack_layer_params)
from skypilot_tpu.parallel.train import default_optimizer, next_token_loss

CFG = GPTConfig(vocab_size=256, block_size=64, num_layers=4,
                num_heads=4, embed_dim=64, dtype=jnp.float32,
                logits_dtype=jnp.float32)


@pytest.fixture(scope='module')
def setup():
    model = GPT(CFG)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(stage=4, data=2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0,
                                CFG.vocab_size, jnp.int32)
    return model, params, mesh, tokens


def test_stack_roundtrip(setup):
    _, params, _, _ = setup
    stacked, rest = stack_layer_params(params, 'h_', CFG.num_layers)
    for leaf in jax.tree.leaves(stacked):
        assert leaf.shape[0] == CFG.num_layers
    back = unstack_layer_params(stacked, rest, 'h_', CFG.num_layers)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_pipeline_loss_matches_sequential(setup):
    model, params, mesh, tokens = setup
    pp = PipelinedGPT(model, mesh, num_microbatches=4)
    stacked, rest = pp.split_params(params)
    ref = next_token_loss(model.apply({'params': params}, tokens), tokens)
    got = pp.loss(stacked, rest, tokens)
    np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)
    # Microbatch count must not change the answer (mean of equal-size
    # microbatch means == full-batch mean).
    got2 = PipelinedGPT(model, mesh, num_microbatches=8).loss(
        stacked, rest, tokens)
    np.testing.assert_allclose(float(got2), float(ref), rtol=2e-5)


@pytest.mark.slow
def test_pipeline_grads_match_sequential(setup):
    """jax.grad through the scan + ppermutes reproduces sequential
    gradients for BOTH the stage-sharded stacks and the shared
    embeddings/head (wte is tied: embed + head grads combine)."""
    model, params, mesh, tokens = setup
    pp = PipelinedGPT(model, mesh, num_microbatches=4)
    stacked, rest = pp.split_params(params)

    ref_grads = jax.grad(lambda p: next_token_loss(
        model.apply({'params': p}, tokens), tokens))(params)
    ref_stacked, ref_rest = stack_layer_params(ref_grads, 'h_',
                                               CFG.num_layers)
    g_stacked, g_rest = jax.grad(
        lambda s, r: pp.loss(s, r, tokens), argnums=(0, 1))(stacked, rest)
    for a, b in zip(jax.tree.leaves(ref_stacked),
                    jax.tree.leaves(g_stacked)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)
    for a, b in zip(jax.tree.leaves(ref_rest), jax.tree.leaves(g_rest)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_pipeline_train_step_descends(setup):
    model, _, mesh, tokens = setup
    pp = PipelinedGPT(model, mesh, num_microbatches=4)
    tx = default_optimizer()
    state = pp.init(jax.random.PRNGKey(0), tokens, tx)
    # Stage shards actually land on the stage axis.
    stacked, _ = state.params
    leaf = jax.tree.leaves(stacked)[0]
    assert 'stage' in str(leaf.sharding.spec)
    step = pp.make_train_step(tx)
    state, loss0 = step(state, tokens)
    for _ in range(3):
        state, loss = step(state, tokens)
    assert float(loss) < float(loss0)
    assert int(state.step) == 4


@pytest.mark.slow
def test_uneven_layers_pad_to_stages(setup):
    """num_layers % stages != 0: the stack zero-pads and the padded
    slots are masked to identity — the loss still matches the
    sequential model (4 layers over 8 stages: half the slots pad)."""
    model, params, _, tokens = setup
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(stage=8))
    pp = PipelinedGPT(model, mesh, num_microbatches=4)
    assert pp.layers_per_stage == 1 and pp.padded_layers == 8
    stacked, rest = pp.split_params(params)
    assert jax.tree.leaves(stacked)[0].shape[0] == 8
    ref = next_token_loss(model.apply({'params': params}, tokens),
                          tokens)
    np.testing.assert_allclose(float(pp.loss(stacked, rest, tokens)),
                               float(ref), rtol=2e-5)
    # Round-trip drops the padding.
    back = pp.merge_params(stacked, rest)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_train_lm_pipeline_cli(tmp_path):
    """The product surface: train_lm --pipeline-stages runs end-to-end
    on a stage x data mesh, checkpoints the (stacked, rest) state, and
    RESUMES from it."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    env['PYTHONPATH'] = f"{repo}:{env.get('PYTHONPATH', '')}"
    base = [sys.executable, '-m', 'skypilot_tpu.recipes.train_lm',
            '--cpu', '--model', 'tiny', '--pipeline-stages', '2',
            '--seq', '64', '--global-batch', '32', '--log-every', '2',
            '--ckpt-dir', str(tmp_path / 'ckpt'), '--ckpt-every', '2']
    out = subprocess.run(base + ['--steps', '2'], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'stage=2' in out.stdout
    out = subprocess.run(base + ['--steps', '4'], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'resumed from checkpoint step 2' in out.stdout


@pytest.mark.slow
def test_train_lm_pipeline_with_tensor_cli(tmp_path):
    """dp x pp x tp from the CLI: v2 shards tensor WITHIN stages."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    env['PYTHONPATH'] = f"{repo}:{env.get('PYTHONPATH', '')}"
    out = subprocess.run(
        [sys.executable, '-m', 'skypilot_tpu.recipes.train_lm',
         '--cpu', '--model', 'tiny', '--pipeline-stages', '2',
         '--tensor', '2', '--seq', '64', '--global-batch', '32',
         '--log-every', '2', '--steps', '2'],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'stage=2, tensor=2' in out.stdout
    assert 'training done' in out.stdout


@pytest.mark.slow
def test_pipeline_llama_matches_sequential():
    """The Llama family pipelines too: loss AND grads match the
    sequential model (rope/GQA blocks, untied head, RMSNorm)."""
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    from skypilot_tpu.parallel.pipeline import PipelinedLM
    cfg = LlamaConfig(vocab_size=256, max_seq_len=64, num_layers=4,
                      num_heads=4, num_kv_heads=2, embed_dim=64,
                      mlp_dim=128, dtype=jnp.float32,
                      logits_dtype=jnp.float32)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(stage=4, data=2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0,
                                cfg.vocab_size, jnp.int32)
    pp = PipelinedLM(model, mesh, num_microbatches=4)
    stacked, rest = pp.split_params(params)
    ref = next_token_loss(model.apply({'params': params}, tokens), tokens)
    got = pp.loss(stacked, rest, tokens)
    np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)

    ref_grads = jax.grad(lambda p: next_token_loss(
        model.apply({'params': p}, tokens), tokens))(params)
    ref_stacked, ref_rest = stack_layer_params(ref_grads, 'layer_', 4)
    g_stacked, g_rest = jax.grad(
        lambda s, r: pp.loss(s, r, tokens), argnums=(0, 1))(stacked, rest)
    for a, b in zip(jax.tree.leaves(ref_stacked),
                    jax.tree.leaves(g_stacked)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=3e-4, atol=3e-5)
    for a, b in zip(jax.tree.leaves(ref_rest), jax.tree.leaves(g_rest)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.slow
def test_pipeline_tp_within_stages():
    """dp x pp x tp: tensor parallelism composes INSIDE pipeline
    stages (v2) — block leaves shard over `tensor` on their logical
    inner dims while the stack dim shards over `stage`, and the loss
    still matches the sequential model when params enter with those
    placements (GSPMD handles the within-stage collectives under the
    shard_map's auto axes)."""
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    from skypilot_tpu.parallel.pipeline import PipelinedLM
    cfg = LlamaConfig(vocab_size=256, max_seq_len=64, num_layers=4,
                      num_heads=4, num_kv_heads=2, embed_dim=64,
                      mlp_dim=128, dtype=jnp.float32,
                      logits_dtype=jnp.float32)
    model = Llama(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshConfig(stage=2, tensor=2, data=2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0,
                                cfg.vocab_size, jnp.int32)
    pp = PipelinedLM(model, mesh, num_microbatches=4)
    stacked, rest = pp.split_params(params)
    s_stacked, s_rest = pp.param_shardings(stacked, rest)
    # The derived shardings really put tensor on inner dims (an MLP
    # or attention kernel) and stage on the stack dim.
    specs = [s.spec for s in jax.tree.leaves(s_stacked)]
    assert all(spec[0] == 'stage' for spec in specs)
    assert any('tensor' in str(spec[1:]) for spec in specs), specs
    # Vocab tables stage-shard (not replicated per stage).
    assert 'stage' in str(s_rest['tok_embed'].spec)
    assert 'stage' in str(s_rest['lm_head'].spec)

    stacked = jax.device_put(stacked, s_stacked)
    rest = jax.device_put(rest, s_rest)
    ref = next_token_loss(model.apply({'params': params}, tokens),
                          tokens)
    np.testing.assert_allclose(float(pp.loss(stacked, rest, tokens)),
                               float(ref), rtol=3e-5)

    # And it trains: init born-sharded + a few descending steps.
    tx = default_optimizer()
    state = pp.init(jax.random.PRNGKey(0), tokens, tx)
    step = pp.make_train_step(tx)
    state, l0 = step(state, tokens)
    for _ in range(3):
        state, l1 = step(state, tokens)
    assert float(l1) < float(l0)


def test_pipeline_rejects_unsupported_family():
    from skypilot_tpu.parallel.pipeline import PipelinedLM

    class NotAModel:
        config = None

    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(stage=2, data=4))
    with pytest.raises(ValueError, match='DeepSeek families'):
        PipelinedLM(NotAModel(), mesh)


@pytest.mark.slow
def test_pipeline_deepseek_matches_sequential():
    """DeepSeek (MLA) pipelines too: llama-shaped at the pipeline
    seam; loss matches the sequential model."""
    from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
    from skypilot_tpu.parallel.pipeline import PipelinedLM
    import dataclasses
    cfg = dataclasses.replace(DeepseekConfig.tiny(),
                              dtype=jnp.float32,
                              logits_dtype=jnp.float32)
    model = Deepseek(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(stage=2, data=4))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0,
                                cfg.vocab_size, jnp.int32)
    pp = PipelinedLM(model, mesh, num_microbatches=4)
    stacked, rest = pp.split_params(params)
    ref = next_token_loss(model.apply({'params': params}, tokens),
                          tokens)
    np.testing.assert_allclose(float(pp.loss(stacked, rest, tokens)),
                               float(ref), rtol=3e-5)
    # Gradients flow end to end: one step descends.
    tx = default_optimizer()
    state = pp.init(jax.random.PRNGKey(0), tokens, tx)
    step = pp.make_train_step(tx)
    state, l0 = step(state, tokens)
    for _ in range(3):
        state, l1 = step(state, tokens)
    assert float(l1) < float(l0)


@pytest.mark.slow
def test_tick_remat_preserves_loss_and_grads(setup):
    """Per-tick rematerialization (the pipeline's memory profile)
    changes nothing numerically."""
    from skypilot_tpu.parallel.pipeline import PipelinedLM
    model, params, mesh, tokens = setup
    on = PipelinedLM(model, mesh, num_microbatches=4, remat_ticks=True)
    off = PipelinedLM(model, mesh, num_microbatches=4,
                      remat_ticks=False)
    stacked, rest = on.split_params(params)
    np.testing.assert_allclose(float(on.loss(stacked, rest, tokens)),
                               float(off.loss(stacked, rest, tokens)),
                               rtol=1e-6)
    g_on = jax.grad(lambda s: on.loss(s, rest, tokens))(stacked)
    g_off = jax.grad(lambda s: off.loss(s, rest, tokens))(stacked)
    for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.slow
def test_pipeline_mixtral_matches_per_microbatch_reference():
    """Mixtral pipelines with exact equality to the sequential model
    evaluated per microbatch (the router aux is a product of
    batch-means, so the faithful reference is the mean of per-
    microbatch losses; with M=1 this IS the full-batch loss)."""
    from skypilot_tpu.models.mixtral import (Mixtral, MixtralConfig,
                                             moe_next_token_loss)
    from skypilot_tpu.parallel.pipeline import PipelinedLM
    cfg = MixtralConfig(vocab_size=256, max_seq_len=64, num_layers=4,
                        num_heads=4, num_kv_heads=2, embed_dim=64,
                        mlp_dim=96, num_experts=4, experts_per_token=2,
                        dtype=jnp.float32, logits_dtype=jnp.float32)
    model = Mixtral(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))['params'])
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(stage=4, data=2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size, jnp.int32)

    # M=1: pipeline loss == sequential full-batch loss EXACTLY.
    pp1 = PipelinedLM(model, mesh, num_microbatches=1)
    stacked, rest = pp1.split_params(params)
    ref_full = moe_next_token_loss(
        model.apply({'params': params}, tokens), tokens)
    np.testing.assert_allclose(float(pp1.loss(stacked, rest, tokens)),
                               float(ref_full), rtol=3e-4)

    # M=4: pipeline == mean of per-microbatch sequential losses.
    pp4 = PipelinedLM(model, mesh, num_microbatches=4)
    mbs = tokens.reshape(4, 2, 32)
    ref_mb = np.mean([float(moe_next_token_loss(
        model.apply({'params': params}, mb), mb)) for mb in mbs])
    np.testing.assert_allclose(float(pp4.loss(stacked, rest, tokens)),
                               ref_mb, rtol=3e-4)

    # Gradients flow (router included): one step descends.
    from skypilot_tpu.parallel.train import default_optimizer
    tx = default_optimizer()
    state = pp4.init(jax.random.PRNGKey(0), tokens, tx)
    step = pp4.make_train_step(tx)
    state, l0 = step(state, tokens)
    for _ in range(3):
        state, l1 = step(state, tokens)
    assert float(l1) < float(l0)
