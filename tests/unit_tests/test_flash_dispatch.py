"""Flash-attention dispatch under sharded meshes.

The pallas kernel itself is TPU-only; these tests inject a plain
attention kernel into `_flash` to validate the GSPMD-safety wrapper:
on a multi-device mesh the kernel must run under shard_map (batch over
data/fsdp, heads over tensor) and match the XLA reference exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import attention as attn
from skypilot_tpu.parallel import mesh as mesh_lib


def _plain_kernel(q, k, v, causal):
    return jax.nn.dot_product_attention(q, k, v, is_causal=causal)


def _rand_qkv(batch=8, seq=64, heads=4, dim=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, dim)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def test_flash_shard_map_matches_reference():
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2, fsdp=2,
                                                  tensor=2))
    q, k, v = _rand_qkv()
    ref = _plain_kernel(q, k, v, True)
    with mesh:
        out = attn._flash(q, k, v, causal=True, kernel=_plain_kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_flash_shard_map_inside_jit_sharded():
    """The real usage: inside jit with sharded operands."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2, fsdp=2,
                                                  tensor=2))
    q, k, v = _rand_qkv()
    ref = _plain_kernel(q, k, v, True)

    def f(q, k, v):
        return attn._flash(q, k, v, causal=True, kernel=_plain_kernel)

    with mesh:
        sharded = tuple(
            jax.device_put(
                x, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(
                        ('data', 'fsdp'), None, 'tensor', None)))
            for x in (q, k, v))
        out = jax.jit(f)(*sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_flash_gqa_expansion_under_mesh():
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2, fsdp=4))
    q, _, _ = _rand_qkv(heads=4)
    _, k, v = _rand_qkv(heads=4)
    k2, v2 = k[:, :, :2], v[:, :, :2]  # 2 kv heads for 4 q heads
    k_exp = jnp.repeat(k2, 2, axis=2)
    v_exp = jnp.repeat(v2, 2, axis=2)
    ref = _plain_kernel(q, k_exp, v_exp, True)
    with mesh:
        out = attn._flash(q, k2, v2, causal=True, kernel=_plain_kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def test_flash_says_so_when_batch_indivisible():
    """Batch 3 can't split over 8 shards: 'auto' is told so ONCE at
    trace time (a warning naming the XLA route it takes instead), and
    asking for the kernel by name raises — nothing quietly returns
    None for the caller to paper over."""
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2, fsdp=4))
    q, k, v = _rand_qkv(batch=3)
    with mesh:
        with pytest.warns(UserWarning, match='flash attention not used'):
            assert attn._flash_shardable(q) is False
        with pytest.raises(ValueError, match='does not divide'):
            attn._flash(q, k, v, causal=True, kernel=_plain_kernel)
        q8, _, _ = _rand_qkv(batch=8)
        assert attn._flash_shardable(q8) is True


def test_flash_no_mesh_runs_kernel_directly():
    q, k, v = _rand_qkv(batch=2)
    calls = []

    def spy_kernel(q, k, v, causal):
        calls.append('direct')
        return _plain_kernel(q, k, v, causal)

    out = attn._flash(q, k, v, causal=False, kernel=spy_kernel)
    assert calls == ['direct']
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_plain_kernel(q, k, v, False)),
                               atol=1e-6)
