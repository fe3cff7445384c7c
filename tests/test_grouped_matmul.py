"""The Pallas grouped matmul (``ops/grouped_matmul.py``) against
``lax.ragged_dot``, interpreted on the CPU, and the tiles it picks at the
served expert layers' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from torchgpipe_tpu.ops import grouped_matmul as module
from torchgpipe_tpu.ops.grouped_matmul import (
    _VMEM_BUDGET,
    grouped_matmul,
    tiles,
    transposes,
)

# (m, k, n, group_sizes): rows, their width, the banks' width, the groups.
CASES = {
    # n = 96 is not a multiple of 128: the bank is read transposed.
    "transposed": (256, 256, 96, [60, 70, 0, 100]),
    "skewed": (384, 128, 256, [3, 350, 1, 2, 0, 4]),
    "empty-groups": (256, 128, 128, [0, 120, 0, 0, 90, 0]),
    "all-empty": (128, 128, 128, [0, 0, 0]),
    # 40 + 30 of 256 rows in a group: the rest are no group's.
    "rows-past-last-group": (256, 128, 128, [40, 0, 30]),
    # 300 rows: tm = 128 does not divide them, the kernel pads.
    "rows-not-tiled": (300, 256, 128, [50, 0, 120, 130]),
    # Under a budget of 300,000 bytes only 128 x 128 weight tiles fit: the
    # products accumulate over three k tiles, in two n tiles.
    "k-and-n-tiled": (256, 384, 256, [100, 0, 100, 56]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matmul_matches_ragged_dot(case, monkeypatch):
    """On every row that lies in a group, bf16 in and bf16 out, the kernel
    equals ``lax.ragged_dot`` of the same values in float32 to bf16's
    rounding; rows past the last group are not compared (they hold
    anything, as the compiler's grouped product leaves them)."""
    m, k, n, sizes = CASES[case]
    if case == "k-and-n-tiled":
        monkeypatch.setattr(module, "_VMEM_BUDGET", 300_000)
        assert tiles(m, k, n, len(sizes)) == (128, 128, 128)
    assert transposes(k, n) == (case == "transposed")
    tm = tiles(m, k, n, len(sizes))[0]
    assert (m % tm != 0) == (case == "rows-not-tiled")
    ks = jax.random.split(jax.random.PRNGKey(m + k + n), 2)
    x = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (len(sizes), k, n))
         * k ** -0.5).astype(jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(x, w, gs, interpret=True)
    assert got.shape == (m, n) and got.dtype == jnp.bfloat16
    want = lax.ragged_dot(x.astype(jnp.float32), w.astype(jnp.float32), gs)
    held = sum(sizes)
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held]),
        rtol=2e-2, atol=2e-2)


# The served expert layers' products: (m, k, n, held experts) -> tiles.
SERVED = {
    "nemotron.decode.up": ((3072, 2688, 1856, 64), (128, 896, 1856)),
    "nemotron.decode.down": ((3072, 1856, 2688, 64), (128, 1856, 896)),
    "nemotron.prefill.up": ((39168, 2688, 1856, 64), (256, 896, 1856)),
    "nemotron.prefill.down": ((39168, 1856, 2688, 64), (256, 1856, 896)),
    "axk1.decode.up": ((1024, 7168, 2048, 12), (128, 1024, 2048)),
    "axk1.prefill.down": ((6400, 2048, 7168, 12), (256, 2048, 1024)),
    "trinity.decode.up": ((192, 3072, 3072, 32), (128, 1536, 1536)),
}


@pytest.mark.parametrize("product", sorted(SERVED))
def test_tiles_follow_the_shape(product):
    """A width that is not a multiple of 128 is one tile; every other the
    largest multiple of 128 dividing it that fits; 256 rows a tile where
    a group averages that many, else 128; the double-buffered blocks and
    the float32 accumulator fit the budget."""
    (m, k, n, groups), want = SERVED[product]
    tm, tk, tn = tiles(m, k, n, groups)
    assert (tm, tk, tn) == want
    assert k % tk == 0 and n % tn == 0
    assert 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn <= _VMEM_BUDGET
