"""`plane._segment_bounds` against numpy's searchsorted, bit for bit.

The routing stage and `ingest` bound each destination's segment of the
sorted bucket ids with `_segment_bounds(keys, n)`, which must equal
`np.searchsorted(keys, arange(n + 1))`. Cases: the benchmark's shape
(32,768 buckets over 524,288 slots), shapes smaller than one row or
one block, rows narrower than 128 (k x k just covers the keys), key
counts that are no multiple of the k x k block, the widest grown ring
(256 slots a host), and the skews the plane makes: every slot in the
sentinel bucket n (nothing sent), every slot in one bucket, and most
buckets empty.
"""

import jax
import numpy as np
import pytest

from shadow_tpu.tpu import plane


def _keys(rng, n, b, kind):
    if kind == "busy":  # three in four slots sent, the rest to n
        k = np.where(rng.random(b) < 0.75, rng.integers(0, n, b), n)
    elif kind == "sentinel":
        k = np.full(b, n)
    elif kind == "one":
        k = np.full(b, n // 2)
    elif kind == "sparse":
        k = rng.choice([0, n // 3, n - 1, n], b)
    return np.sort(k).astype(np.int32)


CASES = [
    (32_768, 524_288, "busy"),
    (7, 5, "busy"),
    (300, 100, "busy"),
    (1_000, 70_001, "busy"),
    (300, 4_096, "busy"),
    (300, 4_097, "busy"),
    (300, 16_384, "busy"),
    (300, 300 * 256, "busy"),
    (1_000, 16_384 + 1, "sparse"),
    (32_768, 524_288, "sentinel"),
    (300, 70_001, "sentinel"),
    (32_768, 524_288, "one"),
    (7, 5, "one"),
    (32_768, 524_288, "sparse"),
    (300, 100, "sparse"),
]


@pytest.mark.parametrize("n,b,kind", CASES,
                         ids=[f"N{n}-B{b}-{k}" for n, b, k in CASES])
def test_segment_bounds_matches_searchsorted(n, b, kind):
    keys = _keys(np.random.default_rng(n + b), n, b, kind)
    got = jax.jit(plane._segment_bounds, static_argnums=1)(keys, n)
    want = np.searchsorted(keys, np.arange(n + 1))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got), want)
