"""`train_lm --dump-step N FILE`: what a plain reference needs to
check one step of the trainer (PERF.md section 7): the step's input
tokens, the parameters before its update and what the step then
reported, in one .npz."""
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_dumped_step_reproduces_with_the_models_own_forward_pass(
        tmp_path):
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    import optax

    from skypilot_tpu.parallel.train import next_token_loss
    from skypilot_tpu.recipes.train_lm import _build_model

    path = tmp_path / 'step1.npz'
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    out = subprocess.run(
        [sys.executable, '-m', 'skypilot_tpu.recipes.train_lm',
         '--cpu', '--model', 'tiny', '--steps', '3', '--seq', '16',
         '--global-batch', '4', '--log-every', '1',
         '--dump-step', '1', str(path)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stdout + out.stderr
    assert f'dump: step 1 -> {path}' in out.stdout
    data = np.load(path)
    assert int(data['step']) == 1
    tokens = data['tokens']
    assert tokens.shape == (4, 16) and tokens.dtype == np.int32

    # The parameters back into the model's own tree, by path.
    model, _, _ = _build_model('tiny', 16, remat=False)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))['params']
    import flax.linen as nn
    shapes = nn.meta.unbox(shapes)
    keys = {k for k in data.files if k.startswith('param:')}
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    assert keys == {f'param:{jax.tree_util.keystr(p)}' for p, _ in flat}
    leaves = [data[f'param:{jax.tree_util.keystr(p)}'] for p, _ in flat]
    assert all(x.dtype == np.float32 and x.shape == s.shape
               for x, (_, s) in zip(leaves, flat))
    params = jax.tree_util.tree_unflatten(treedef, leaves)

    def loss_of(p):
        return next_token_loss(model.apply({'params': p}, tokens),
                               tokens)

    loss, grads = jax.value_and_grad(loss_of)(params)
    # The step's own loss is the fused blockwise one with an f32
    # accumulator; the plain head rounds its logits to the model's
    # bf16, so the two agree to bf16's three digits.
    np.testing.assert_allclose(float(loss), float(data['loss']),
                               rtol=2e-2)
    np.testing.assert_allclose(float(optax.global_norm(grads)),
                               float(data['grad_norm']), rtol=5e-2)
    # It is step 1's loss and no other's: the printed log agrees.
    line = next(l for l in out.stdout.splitlines()
                if l.startswith('step 2/3 '))
    assert f'loss={float(data["loss"]):.4f}' in line
