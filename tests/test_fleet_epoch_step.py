"""The jax steppers run one epoch advance per scan step, shared by the op
programs; the numpy stepper advances inside each program.  These tests
hold the jax-opcode and unrolled steppers to the numpy stepper, tenant by
tenant and state array by state array, on plans whose advances fall in
different steps for different tenants: both programs advancing in one
step, one program advancing alone, and a tenant that bails in the step
where it would have advanced.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.fleet import jaxexec  # noqa: E402
from repro.fleet.lowering import KIND_DEQ, KIND_ENQ  # noqa: E402
from repro.fleet.state import build_template, replicate  # noqa: E402
from repro.fleet.stepper import EPOCH_ADV_OPS, run_chunk_numpy  # noqa: E402

QUEUES = ["DurableMSQ", "OptLinkedQ", "LinkedQ"]
# crosses EPOCH_ADV_OPS at least twice for every tenant, three times for
# most: the third advance frees the first epoch's limbo entries
OPS = 3 * EPOCH_ADV_OPS + 8
PREFILL, CHUNK = 4, 50
# op counters at the start: two tenants start at each of PAIRS, cross the
# threshold in the same steps, and run one program each at their first
# crossing; a tenant at each of SINGLES crosses it alone
PAIRS = (10, 30, 47)
SINGLES = (0, 5, 21, 38, 52, 63)
BAILER_START = EPOCH_ADV_OPS - 1 - PREFILL   # bails on its advancing op


def _plans(rng):
    """-> (opsctr starts (n,), kinds (OPS, n), the bailing tenant).  A
    tenant's first crossing is its op ``EPOCH_ADV_OPS - 1 - start``.
    Every tenant but the bailer keeps at least two nodes, so each dequeue
    is sound; the bailer drains its prefill and dequeues once more in the
    step where its counter would reach the threshold."""
    starts = [s for s in PAIRS for _ in range(2)] + list(SINGLES)
    kinds = np.zeros((OPS, len(starts) + 1), np.uint8)
    for i, start in enumerate(starts):
        forced = {}
        if start in PAIRS:
            first = EPOCH_ADV_OPS - 1 - start
            forced[first] = KIND_ENQ if i % 2 == 0 else KIND_DEQ
        length = PREFILL
        for j in range(OPS):
            k = forced.get(j, KIND_DEQ if (length > 2 and rng.random() < 0.5)
                           else KIND_ENQ)
            kinds[j, i] = k
            length += 1 if k == KIND_ENQ else -1
    bailer = len(starts)
    kinds[:, bailer] = rng.integers(0, 2, OPS)
    kinds[:PREFILL + 1, bailer] = KIND_DEQ
    starts.append(BAILER_START)
    return np.asarray(starts, np.int32), kinds, bailer


def _numpy_run(t, st, kinds):
    """The numpy stepper one step at a time; -> (the tenants that advance
    in each step, as {step: set of tenants}, whether an advance freed a
    limbo entry)."""
    advanced, freed = {}, False
    for j in range(kinds.shape[0]):
        epoch, nlimbo = st.epoch.copy(), st.nlimbo.copy()
        run_chunk_numpy(t.programs, t.dims, st, kinds[j:j + 1], j)
        moved = np.nonzero(st.epoch != epoch)[0]
        if moved.size:
            advanced[j] = set(moved.tolist())
        freed |= bool((st.nlimbo < nlimbo).any())
    return advanced, freed


@pytest.mark.parametrize("make", ["make_opcode_chunk_fn", "make_chunk_fn"])
@pytest.mark.parametrize("queue", QUEUES)
def test_one_advance_per_step_matches_the_numpy_stepper(queue, make):
    t = build_template(queue, "optane-clwb", OPS, PREFILL)
    assert all(p.uses_ssmem for p in t.programs)
    rng = np.random.default_rng(17)
    starts, kinds, bailer = _plans(rng)
    n = kinds.shape[1]
    ref = replicate(t.row, t.dims, n)
    ref.opsctr[:] = starts
    # copies: a jax array on the CPU may share a numpy array's memory,
    # and the numpy stepper writes in place
    st = {k: jax.numpy.asarray(v.copy())
          for k, v in jaxexec.state_arrays(ref).items()}
    advanced, freed = _numpy_run(t, ref, kinds)

    # the plans hold the three cases they are for
    both = [j for j, who in advanced.items()
            if {int(kinds[j, i]) for i in who} == {KIND_ENQ, KIND_DEQ}]
    alone = [j for j, who in advanced.items()
             if len({int(kinds[j, i]) for i in who}) == 1]
    assert len(both) >= len(PAIRS) and len(alone) >= len(SINGLES)
    assert all(sum(i in who for who in advanced.values()) >= 2
               for i in range(n - 1))
    assert int(ref.bail_at[bailer]) == PREFILL
    assert bailer not in set().union(*advanced.values())
    assert (ref.bail_at[:bailer] == -1).all()
    assert freed

    fn = jax.jit(getattr(jaxexec, make)(jax, t.programs, t.dims))
    for start in range(0, OPS, CHUNK):
        kc = np.ascontiguousarray(kinds[start:start + CHUNK].T)
        oi = np.arange(start, start + kc.shape[1], dtype=np.int32)
        st = fn(st, kc, oi)
    want = jaxexec.state_arrays(ref)
    assert sorted(st) == sorted(want)
    for k in want:
        got = np.asarray(st[k])
        for i in range(n):
            np.testing.assert_array_equal(got[i], want[k][i],
                                          err_msg=f"{k}, tenant {i}")
