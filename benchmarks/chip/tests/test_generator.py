"""The benchmark's copy of the plan generator draws the program's plans."""
import json

import numpy as np
import pytest

import generator
from conftest import HERE
from repro.fleet import fleet_kinds


@pytest.mark.parametrize("tenants,ops,seed", [
    (1, 96, 0), (1000, 96, 7), (257, 33, 2**31 + 12345), (64, 200, 2**40)])
def test_generator_matches_fleet_kinds(tenants, ops, seed):
    traffic = json.loads((HERE / "traffic" / "mixed5050.json").read_text())
    got = generator.generate(tenants, ops, seed, traffic)
    want = fleet_kinds(tenants, ops, seed=seed, prefill=traffic["prefill"],
                       p_deq=traffic["p_deq"])
    assert got.dtype == want.dtype and np.array_equal(got, want)


def test_no_tenant_dequeues_an_empty_queue():
    traffic = {"p_deq": 0.9, "prefill": 2}
    kinds = generator.generate(500, 64, 3, traffic).astype(np.int64)
    length = 2 + np.cumsum(1 - 2 * kinds, axis=0)
    assert length.min() >= 0
