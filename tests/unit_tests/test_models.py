"""Sharded model tests on the 8-device CPU mesh (slow: real compiles)."""
import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models.gpt import GPT, GPTConfig
from skypilot_tpu.models.llama import Llama, LlamaConfig
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel.train import (ShardedTrainer, next_token_loss,
                                         shard_batch)


def test_loss_math():
    logits = jnp.zeros((2, 4, 8))
    tokens = jnp.zeros((2, 4), jnp.int32)
    loss = next_token_loss(logits, tokens)
    assert loss == pytest.approx(jnp.log(8), rel=1e-5)


@pytest.mark.slow
def test_gpt_trains_on_mesh(cpu_mesh8):
    model = GPT(GPTConfig.tiny())
    tokens = jnp.ones((8, 64), jnp.int32)
    trainer = ShardedTrainer(model, cpu_mesh8)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    # The embedding table shards over tensor (vocab dim) but NOT fsdp:
    # fsdp-sharding its embed dim forces an involuntary full-remat
    # reshard in the gather's backward (see mesh.DEFAULT_RULES).
    wte_spec = str(state.params['wte'].sharding.spec)
    assert 'tensor' in wte_spec and 'fsdp' not in wte_spec
    # FSDP still shards the dense kernels' embed dim.
    fc_spec = str(state.params['h_0']['mlp']['c_fc']['kernel']
                  .sharding.spec)
    assert 'fsdp' in fc_spec
    step = trainer.make_train_step(tokens)
    batch = shard_batch(tokens, cpu_mesh8)
    state, l1 = step(state, batch)
    state, l2 = step(state, batch)
    assert float(l2) < float(l1)
    assert int(state.step) == 2


@pytest.mark.slow
def test_llama_trains_on_mesh(cpu_mesh8):
    model = Llama(LlamaConfig.tiny())
    tokens = jnp.ones((8, 64), jnp.int32)
    trainer = ShardedTrainer(model, cpu_mesh8)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    step = trainer.make_train_step(tokens)
    batch = shard_batch(tokens, cpu_mesh8)
    state, l1 = step(state, batch)
    state, l2 = step(state, batch)
    assert float(l2) < float(l1)


@pytest.mark.slow
def test_gqa_shapes():
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    tokens = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


@pytest.mark.slow
def test_mixtral_expert_parallel_trains():
    from skypilot_tpu.models.mixtral import (Mixtral, MixtralConfig,
                                             moe_next_token_loss)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2, expert=4))
    cfg = MixtralConfig.tiny()
    model = Mixtral(cfg)
    tokens = jnp.ones((8, 64), jnp.int32)
    trainer = ShardedTrainer(model, mesh, loss_fn=moe_next_token_loss)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    # Expert weights actually sharded over the expert axis.
    w_gate = state.params['layer_0']['moe']['w_gate']
    assert 'expert' in str(w_gate.sharding.spec), w_gate.sharding
    step = trainer.make_train_step(tokens)
    batch = shard_batch(
        jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                           cfg.vocab_size, jnp.int32), mesh)
    state, l1 = step(state, batch)
    state, l2 = step(state, batch)
    state, l3 = step(state, batch)
    assert float(l3) < float(l1)


@pytest.mark.slow
def test_checkpoint_save_restore(cpu_mesh8, tmp_path):
    from skypilot_tpu.parallel.checkpoints import CheckpointManager
    model = GPT(GPTConfig.tiny())
    tokens = jnp.ones((8, 64), jnp.int32)
    trainer = ShardedTrainer(model, cpu_mesh8)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    step = trainer.make_train_step(tokens)
    batch = shard_batch(tokens, cpu_mesh8)
    state, _ = step(state, batch)

    mgr = CheckpointManager(str(tmp_path / 'ckpt'))
    assert mgr.latest_step() is None
    mgr.save(int(state.step), state)
    mgr.wait_until_finished()
    assert mgr.latest_step() == 1

    restored = mgr.restore(state)
    assert int(restored.step) == int(state.step)
    import numpy as np
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.params['wte'])),
        np.asarray(jax.device_get(state.params['wte'])))
    # Restored state keeps the mesh shardings (resume training works).
    state2, loss = step(restored, batch)
    assert float(loss) > 0
    mgr.close()


@pytest.mark.slow
def test_multi_step_matches_sequential(cpu_mesh8):
    """make_multi_step (lax.scan inner loop) == N make_train_step calls."""
    from skypilot_tpu.parallel.train import shard_batch_stack
    model = GPT(GPTConfig.tiny())
    example = jnp.ones((8, 32), jnp.int32)
    data = jax.random.randint(jax.random.PRNGKey(3), (3, 8, 32), 0, 512,
                              jnp.int32)

    trainer = ShardedTrainer(model, cpu_mesh8)
    state = trainer.init(jax.random.PRNGKey(0), example)
    step = trainer.make_train_step(example, donate=False)
    seq_losses = []
    for i in range(3):
        state, loss = step(state, shard_batch(data[i], cpu_mesh8))
        seq_losses.append(float(loss))

    state2 = trainer.init(jax.random.PRNGKey(0), example)
    mstep = trainer.make_multi_step(example, 3, donate=False)
    state2, losses = mstep(state2, shard_batch_stack(data, cpu_mesh8))
    assert int(state2.step) == 3
    assert losses.shape == (3,)
    for a, b in zip(seq_losses, losses):
        assert a == pytest.approx(float(b), rel=1e-5)


@pytest.mark.slow
def test_deepseek_mla_trains_on_mesh(cpu_mesh8):
    from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
    model = Deepseek(DeepseekConfig.tiny())
    tokens = jnp.ones((8, 64), jnp.int32)
    trainer = ShardedTrainer(model, cpu_mesh8)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    step = trainer.make_train_step(tokens)
    batch = shard_batch(tokens, cpu_mesh8)
    state, l1 = step(state, batch)
    state, l2 = step(state, batch)
    assert float(l2) < float(l1)


def test_deepseek_latent_cache_is_compressed():
    """The whole point of MLA: cached dims/token = kv_lora_rank +
    rope_head_dim, independent of heads."""
    from skypilot_tpu.models.deepseek import Deepseek, DeepseekConfig
    cfg = DeepseekConfig.tiny(dtype=jnp.float32)
    model = Deepseek(cfg)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        positions=jnp.zeros((2, 1), jnp.int32), decode=True)
    cache = variables['cache']
    lat = cache['layer_0']['attn']['latent_cache']
    rope = cache['layer_0']['attn']['rope_cache']
    assert lat.shape == (2, cfg.max_seq_len, cfg.kv_lora_rank)
    assert rope.shape == (2, cfg.max_seq_len, cfg.rope_head_dim)
    cached_dims = lat.shape[-1] + rope.shape[-1]
    full_kv_dims = 2 * cfg.num_heads * cfg.v_head_dim
    assert cached_dims < full_kv_dims / 2


@pytest.mark.slow
def test_zero1_loss_parity_and_sharding():
    """ZeRO-1 (opt moments sharded over `data`) is step-for-step
    loss-identical to the replicated-moments trainer — the layout
    changes, the math does not. Also covers the multi-step lax.scan
    path (the inner-loop sharding constraint)."""
    import numpy as np
    from skypilot_tpu.parallel.train import (default_optimizer,
                                             shard_batch_stack)
    from skypilot_tpu.models.llama import Llama, LlamaConfig

    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=4, fsdp=2))
    # qwen-tiny flavor, f32 compute: parity is about the UPDATE MATH —
    # f32 removes the bf16 rounding jitter different executables are
    # allowed to have, so the tolerance can stay tight.
    model = Llama(LlamaConfig.tiny(qkv_bias=True, dtype=jnp.float32))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 32), 0, 512,
                                jnp.int32)
    batch = shard_batch(tokens, mesh)
    curves = {}
    for zero1 in (False, True):
        trainer = ShardedTrainer(model, mesh, tx=default_optimizer(),
                                 zero1=zero1)
        state = trainer.init(jax.random.PRNGKey(0), tokens)
        if zero1:
            # The Adam moments really are data-sharded...
            specs = [str(x.sharding.spec)
                     for x in jax.tree.leaves(state.opt_state)]
            assert any("'data'" in s for s in specs), specs
            # ...while params keep their (fsdp/tensor) layout.
            assert not any(
                "'data'" in str(x.sharding.spec)
                for x in jax.tree.leaves(state.params))
        step = trainer.make_train_step(tokens, donate=False)
        losses = []
        for _ in range(5):
            state, loss = step(state, batch)
            losses.append(float(loss))
        curves[zero1] = losses
    np.testing.assert_allclose(curves[True], curves[False], rtol=1e-5)

    # Multi-step (lax.scan) parity under ZeRO-1 — compared against
    # the non-zero1 MULTI-STEP run (scan executables carry their own
    # bf16-level numeric identity vs single steps, zero1 or not).
    stack = jnp.broadcast_to(tokens, (3, *tokens.shape))
    mcurves = {}
    for zero1 in (False, True):
        trainer = ShardedTrainer(model, mesh, tx=default_optimizer(),
                                 zero1=zero1)
        state = trainer.init(jax.random.PRNGKey(0), tokens)
        mstep = trainer.make_multi_step(tokens, 3, donate=False)
        _, mlosses = mstep(state, shard_batch_stack(stack, mesh))
        mcurves[zero1] = [float(x) for x in mlosses]
    np.testing.assert_allclose(mcurves[True], mcurves[False], rtol=1e-5)
    np.testing.assert_allclose(mcurves[True], curves[False][:3],
                               rtol=1e-4)


@pytest.mark.slow
def test_zero1_checkpoint_roundtrip(tmp_path):
    """Sharded opt state survives save->restore, including a LAYOUT
    CHANGE across the boundary (replicated-moments checkpoint into a
    ZeRO-1 template — the `--zero1` flag flip on resume)."""
    import numpy as np
    from skypilot_tpu.parallel.checkpoints import CheckpointManager
    from skypilot_tpu.parallel.train import default_optimizer
    from skypilot_tpu.models.llama import Llama, LlamaConfig

    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=4, fsdp=2))
    model = Llama(LlamaConfig.tiny(qkv_bias=True))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 32), 0, 512,
                                jnp.int32)
    batch = shard_batch(tokens, mesh)

    z1 = ShardedTrainer(model, mesh, tx=default_optimizer(), zero1=True)
    state = z1.init(jax.random.PRNGKey(0), tokens)
    step = z1.make_train_step(tokens, donate=False)
    state, _ = step(state, batch)

    mgr = CheckpointManager(str(tmp_path / 'ckpt'))
    mgr.save(int(state.step), state, force=True)
    mgr.wait_until_finished()

    # Round-trip into the sharded template: values AND layout.
    restored = mgr.restore(state)
    mu_path = lambda s: jax.tree.leaves(s.opt_state)
    for got, want in zip(mu_path(restored), mu_path(state)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(got)),
                                      np.asarray(jax.device_get(want)))
        assert got.sharding == want.sharding
    # Resume training from the restored sharded state.
    state2, loss = step(restored, batch)
    assert np.isfinite(float(loss))
    mgr.close()

    # Cross-layout restore: checkpoint written WITHOUT zero1, resumed
    # WITH it (orbax reshards on read; fallback re-places if not).
    base = ShardedTrainer(model, mesh, tx=default_optimizer())
    bstate = base.init(jax.random.PRNGKey(0), tokens)
    bstep = base.make_train_step(tokens, donate=False)
    bstate, _ = bstep(bstate, batch)
    mgr2 = CheckpointManager(str(tmp_path / 'ckpt2'))
    mgr2.save(int(bstate.step), bstate, force=True)
    mgr2.wait_until_finished()
    z1_template = z1.init(jax.random.PRNGKey(1), tokens)
    cross = mgr2.restore(z1_template)
    for got, want in zip(mu_path(cross), mu_path(bstate)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(got)),
                                      np.asarray(jax.device_get(want)))
    for got, want in zip(mu_path(cross), mu_path(z1_template)):
        assert got.sharding == want.sharding
    mgr2.close()


def test_overlap_requires_zero1_and_flag_list():
    """--overlap contract: the trainer rejects overlap without the
    ZeRO-1 layout it buckets onto, and the compiler flags are a list
    for LIBTPU_INIT_ARGS (the TPU compiler is inside libtpu; the
    host-side XLA_FLAGS parser aborts on --xla_tpu_* flags)."""
    from skypilot_tpu.parallel.train import OVERLAP_LIBTPU_FLAGS
    model = Llama(LlamaConfig.tiny(dtype=jnp.float32))
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=4, fsdp=2))
    with pytest.raises(ValueError, match='zero1'):
        ShardedTrainer(model, mesh, overlap=True)
    assert all(f.startswith('--xla') for f in OVERLAP_LIBTPU_FLAGS)


def test_overlap_grad_buckets_follow_zero1_layout():
    """Each grad leaf's bucket sharding layers `data` onto the same
    dim the ZeRO-1 moments got — derived via eval_shape, no compile."""
    from jax.sharding import NamedSharding
    model = Llama(LlamaConfig.tiny(dtype=jnp.float32))
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=4, fsdp=2))
    tr = ShardedTrainer(model, mesh, zero1=True, overlap=True)
    tokens = jnp.ones((8, 32), jnp.int32)
    tr.state_sharding(tokens)
    assert tr._grad_sharding is not None
    specs = [s.spec for s in jax.tree.leaves(tr._grad_sharding)
             if isinstance(s, NamedSharding)]
    assert specs, 'no grad bucket shardings derived'
    with_data = [s for s in specs if 'data' in str(s)]
    # The big kernels (the reduce-scatter payload) all bucket.
    assert len(with_data) >= len(specs) * 0.8, (len(with_data),
                                                len(specs))


@pytest.mark.slow
def test_overlap_is_loss_identical_under_zero1():
    """overlap=True only changes WHERE the reduce-scatter happens in
    the schedule (per-leaf, inside backward), never the math: the
    loss curve is bit-comparable to the non-overlap ZeRO-1 run."""
    import numpy as np
    from skypilot_tpu.parallel.train import default_optimizer
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=4, fsdp=2))
    model = Llama(LlamaConfig.tiny(qkv_bias=True, dtype=jnp.float32))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 32), 0,
                                512, jnp.int32)
    batch = shard_batch(tokens, mesh)
    curves = {}
    for overlap in (False, True):
        tr = ShardedTrainer(model, mesh, tx=default_optimizer(),
                            zero1=True, overlap=overlap)
        state = tr.init(jax.random.PRNGKey(0), tokens)
        step = tr.make_train_step(tokens, donate=False)
        losses = []
        for _ in range(5):
            state, loss = step(state, batch)
            losses.append(float(loss))
        curves[overlap] = losses
    np.testing.assert_allclose(curves[True], curves[False],
                               rtol=1e-6)
