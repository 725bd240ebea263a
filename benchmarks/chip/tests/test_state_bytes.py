"""The roofline's byte count against the state the program really keeps."""
import pytest

from repro.fleet import build_template
from repro.fleet.jaxexec import state_arrays
from repro.fleet.state import replicate
from state_bytes import chunk_bytes, state_bytes_per_tenant


@pytest.mark.parametrize("queue", ["DurableMSQ", "OptLinkedQ", "MSQ",
                                   "OptUnlinkedQ"])
def test_state_bytes_match_the_state_layout(queue):
    t = build_template(queue, "optane-clwb", 16, 3)
    arrays = state_arrays(replicate(t.row, t.dims, 5))
    assert sum(a.nbytes for a in arrays.values()) == \
        5 * state_bytes_per_tenant(t.dims)


def test_chunk_bytes_count_state_twice_plus_plans():
    t = build_template("DurableMSQ", "optane-clwb", 16, 3)
    one = state_bytes_per_tenant(t.dims)
    assert chunk_bytes(t.dims, 10, 8) == 2 * one * 10 + 10 * 8 + 4 * 8
