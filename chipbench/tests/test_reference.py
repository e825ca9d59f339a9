"""The reference's counter-based uniforms are the program's, bit for bit
(the one piece of the tick the reference must reproduce exactly), and its
bfloat16 rounding is round-to-nearest-even."""
import jax.numpy as jnp
import numpy as np
import pytest

from reference import crowd_tick


@pytest.mark.parametrize("seed,step,n", [(0, 0, 16), (0xDEADBEEF, 7, 1024),
                                         (2 ** 32 - 1, 2 ** 31 - 1, 64)])
def test_uniform_block_matches_program(seed, step, n):
    from repro.core.simfast import _uniform_block
    want = np.asarray(_uniform_block(jnp.uint32(seed), jnp.int32(step), n))
    got = crowd_tick.uniform_block(seed, step, n)
    np.testing.assert_array_equal(got, want)


def test_bfloat16_rounding():
    x = np.asarray([1.0, 1.00390625, 1.01171875, 3.14159, -2.7182817],
                   np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    np.testing.assert_array_equal(crowd_tick._cast(x, "bfloat16"), want)
