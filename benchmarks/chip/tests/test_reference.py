"""The plain reference agrees with the program, and its control does not.

The plain reference imports nothing of the program; here it is held to
the program's numpy fleet stepper at a small size, tenant by tenant, so
that a fault in either shows.  The control (the reference with the
platform's flushes left out) has to disagree on every tenant."""
import json

import numpy as np
import pytest

import generator
from conftest import HERE
from control import control_readings
from reference import tenant_counts
from repro.fleet import FleetConfig, run_fleet
from run_cell import sample_tenants

QUEUES = ["DurableMSQ", "OptLinkedQ"]


def _config(queue, tenants, ops):
    name = "durablemsq-optane-100k" if queue == "DurableMSQ" \
        else "optlinkedq-optane-100k"
    config = json.loads((HERE / "configs" / f"{name}.json").read_text())
    config.update(tenants=tenants, ops_per_tenant=ops, chunk=8)
    return config


def _traffic():
    return json.loads((HERE / "traffic" / "mixed5050.json").read_text())


@pytest.mark.parametrize("queue", QUEUES)
def test_reference_matches_the_fleet(queue):
    traffic, n, ops = _traffic(), 60, 96
    kinds = generator.generate(n, ops, 11, traffic)
    res = run_fleet(FleetConfig(queue=queue, instances=n, ops=ops,
                                prefill=traffic["prefill"], chunk=48,
                                backend="numpy"), kinds=kinds)
    config = _config(queue, n, ops)
    for i in range(n):
        assert np.array_equal(
            tenant_counts(config, traffic["prefill"], kinds[:, i], i),
            res.counts[i]), f"tenant {i}"


@pytest.mark.parametrize("queue", QUEUES)
def test_control_is_not_correct(queue):
    config = _config(queue, 300, 24)
    mismatched, widest = control_readings(config, _traffic(), 1, 5)
    assert mismatched == len(sample_tenants(300, 1, 5)) and widest > 0
