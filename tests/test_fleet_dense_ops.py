"""The jax steppers' per-instance indexing, written as dense array ops.

Under ``vmap`` a gather or scatter with one index per instance, a sort or
a vector scatter, lowers to TPU code that grows with the instance count.
The steppers use one-hot selects and shift networks instead
(``_take``/``_put``/``_bump``, ``_pack``, the epoch advance).  These tests
hold each to the plain indexing or numpy code it replaces, on the CPU.
"""
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.fleet import jaxexec  # noqa: E402
from repro.fleet.state import build_template, replicate  # noqa: E402
from repro.fleet.stepper import _advance  # noqa: E402


@pytest.mark.parametrize("dtype", [np.uint8, np.int32])
def test_take_put_bump_follow_jax_indexing(dtype):
    """Negative indices count from the end; out of range, a read clamps
    and a write is dropped -- as ``x[i]`` and ``x.at[i]`` do."""
    n = 7
    x = jnp.asarray(np.arange(3, 3 + n), dtype)
    for i in range(-n - 3, n + 3):
        ii = jnp.int32(i)
        assert jaxexec._take(jnp, x, ii) == x[ii], i
        np.testing.assert_array_equal(jaxexec._put(jnp, x, ii, 300),
                                      x.at[ii].set(jnp.int32(300)
                                                   .astype(dtype)))
        if i >= 0:
            np.testing.assert_array_equal(jaxexec._bump(jnp, x, ii),
                                          x.at[ii].add(1))


@pytest.mark.parametrize("dtype", [np.uint8, np.int32])
@pytest.mark.parametrize("n", [1, 7, 200])
def test_put_at_the_drop_index_is_the_identity(n, dtype):
    """A masked-out instance writes at ``_DROP``: nothing changes, for any
    value and inside ``vmap`` alike."""
    x = jnp.asarray(np.arange(3, 3 + n) % 251, dtype)
    for v in (0, 1, 250, -1):
        np.testing.assert_array_equal(
            jaxexec._put(jnp, x, jnp.int32(jaxexec._DROP), v), x)
    xs = jnp.stack([x, x + 1])
    at = jnp.where(jnp.asarray([True, False]), 0, jaxexec._DROP)
    got = jax.vmap(partial(jaxexec._put, jnp), in_axes=(0, 0, None))(
        xs, at, 9)
    np.testing.assert_array_equal(got[0], x.at[0].set(9))
    np.testing.assert_array_equal(got[1], xs[1])


@pytest.mark.parametrize("n", [1, 2, 7, 64, 200])
def test_pack_is_a_stable_compaction(n):
    rng = np.random.default_rng(n)
    for p in (0.0, 0.1, 0.5, 0.9, 1.0):
        keep = rng.random(n) < p
        x = rng.integers(-1000, 1000, n).astype(np.int32)
        (got,), occ = jaxexec._pack(jnp, [jnp.asarray(x)], jnp.asarray(keep))
        k = int(keep.sum())
        np.testing.assert_array_equal(np.asarray(occ), np.arange(n) < k)
        np.testing.assert_array_equal(np.asarray(got)[:k], x[keep])


ADV_KEYS = ("epoch", "nlimbo", "limbo_a", "limbo_e", "limbo_k",
            "free_p", "vfree", "nfree", "nvfree")


@pytest.mark.parametrize("seed", range(4))
def test_advance_matches_numpy_stepper(seed):
    """The dense epoch advance frees, pushes and compacts exactly as the
    numpy stepper's argsort/scatter version, for any freed subset (not
    only a prefix of the limbo list) and any mix of advancing rows."""
    t = build_template("OptLinkedQ", "optane-clwb", 96, 10)
    dims, n = t.dims, 64
    st = replicate(t.row, dims, n)
    rng = np.random.default_rng(seed)
    st.epoch[:] = rng.integers(0, 8, n)
    st.nlimbo[:] = rng.integers(0, min(dims.lcap, dims.fcap, dims.vfcap)
                                // 2 + 1, n)
    st.limbo_a[:] = rng.integers(0, 5000, st.limbo_a.shape)
    st.limbo_e[:] = rng.integers(0, 8, st.limbo_e.shape)
    st.limbo_k[:] = rng.integers(0, 2, st.limbo_k.shape)
    st.nfree[:] = rng.integers(0, dims.fcap - st.nlimbo + 1)
    st.nvfree[:] = rng.integers(0, dims.vfcap - st.nlimbo + 1)
    st.free_p[:] = rng.integers(0, 5000, st.free_p.shape)
    st.vfree[:] = rng.integers(0, 5000, st.vfree.shape)
    adv = rng.random(n) < 0.7
    before = {k: jnp.asarray(getattr(st, k).copy()) for k in ADV_KEYS}
    got = jax.vmap(partial(jaxexec._advance_one, jnp, dims))(
        before, jnp.asarray(adv))
    _advance(dims, st, adv)
    assert (st.nlimbo < np.asarray(before["nlimbo"])).any()
    for k in ADV_KEYS:
        np.testing.assert_array_equal(np.asarray(got[k]), getattr(st, k),
                                      err_msg=k)
