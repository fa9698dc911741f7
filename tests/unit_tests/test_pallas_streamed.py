"""ops/pallas_streamed.py: the decode product that reads its matrix
from HBM inside the call, in interpret mode on the CPU (what Mosaic
takes is kernel_check's, on the chip; that memory-space assignment
leaves the matrix in HBM is tests/unit_tests/test_pool_write_aot.py's)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import pallas_streamed


@pytest.mark.parametrize('m,k,n', [(16, 256, 384), (32, 512, 1536),
                                   (16, 2048, 2048)])
def test_the_product_is_xlas(m, k, n):
    """bf16 operands, float32 sums over the tiles in order, one rounding:
    XLA's product to the last bit where one tile holds the matrix, and
    within one bf16 step of it where the sum runs over several."""
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
    w = (0.02 * jax.random.normal(kw, (k, n), jnp.float32)
         ).astype(jnp.bfloat16)
    got = pallas_streamed.streamed_matmul(x, w, interpret=True)
    want = jnp.dot(x, w, preferred_element_type=jnp.float32)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, n)
    tiles = k // pallas_streamed._tile_rows(k, n, 2)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    if tiles == 1:
        assert np.array_equal(np.asarray(got),
                              np.asarray(want.astype(jnp.bfloat16)))
    else:
        assert tiles > 1
        assert np.all(err <= 2.0 ** -7 * np.abs(np.asarray(want)) + 1e-6)


def test_tiles_and_refusals():
    # LFM2's projections: in_proj 128 rows of 6,144 (1.5 MB a tile, 16
    # tiles), out_proj 512 rows of 2,048 (2 MB, 4 tiles).
    assert pallas_streamed._tile_rows(2048, 6144, 2) == 128
    assert pallas_streamed._tile_rows(2048, 2048, 2) == 512
    assert pallas_streamed.refusal(128, 2048, 6144, jnp.bfloat16) is None
    assert 'sublanes' in pallas_streamed.refusal(4, 2048, 2048,
                                                 jnp.bfloat16)
    assert '128 lanes' in pallas_streamed.refusal(16, 2048, 2000,
                                                  jnp.bfloat16)
    assert 'tile' in pallas_streamed.refusal(16, 128, 16384, jnp.float32)
    with pytest.raises(ValueError, match='sublanes'):
        pallas_streamed.streamed_matmul(jnp.zeros((4, 256), jnp.bfloat16),
                                        jnp.zeros((256, 256), jnp.bfloat16),
                                        interpret=True)
