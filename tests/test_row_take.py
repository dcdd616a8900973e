"""`plane._row_take` against numpy's take_along_axis, bit for bit.

`_row_take` applies the window step's per-row permutations as a select
chain at every width. Edge widths (one and two columns), the plane's
rings (16, 32, the 48-wide merge) and what elastic growth makes of them
(up to 256, and past it), every output width the plane uses (the full
row, a ring's 16 lanes out of a wider merge, the cached [N, 1] index),
each payload dtype, and the vmapped ensemble form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.tpu import plane

WIDTHS = (1, 2, 16, 32, 48, 64, 65, 128, 256, 257)
CASES = sorted({(c, k) for c in WIDTHS for k in (c, 16, 1)})
N = 8


def _operands(rng, lead, c, k, dtype):
    if dtype == np.bool_:
        a = rng.random(lead + (N, c)) < 0.5
    else:
        info = np.iinfo(dtype)
        a = rng.integers(info.min, info.max, lead + (N, c), dtype=dtype,
                         endpoint=True)
    return a, rng.integers(0, c, lead + (N, k), dtype=np.int32)


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.bool_],
                         ids=["int32", "uint32", "bool"])
@pytest.mark.parametrize("c,k", CASES, ids=[f"C{c}-K{k}" for c, k in CASES])
def test_row_take_matches_take_along_axis(c, k, dtype, vmapped):
    rng = np.random.default_rng(1000 * c + k)
    lead = (3,) if vmapped else ()
    a, perm = _operands(rng, lead, c, k, dtype)
    fn = jax.vmap(plane._row_take) if vmapped else plane._row_take
    got = np.asarray(jax.jit(fn)(jnp.asarray(a), jnp.asarray(perm)))
    want = np.take_along_axis(a, perm, axis=-1)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("c", [16, 257])
def test_row_take_is_a_pure_selection(c):
    # SL505's selection witness accepts only these primitives on the
    # permuted operand; a gather or a dtype convert would break the
    # proved `lax.cond` gates of `_compact_ingress` and `_egress_order`
    a = jnp.zeros((N, c), jnp.bool_)
    perm = jnp.zeros((N, 16), jnp.int32)
    eqns = jax.make_jaxpr(plane._row_take)(a, perm).jaxpr.eqns
    assert {e.primitive.name for e in eqns} <= {
        "slice", "broadcast_in_dim", "eq", "select_n"}
