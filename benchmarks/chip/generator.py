"""The one traffic generator: tenant plans from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) holds the mix's parameters:

* ``p_deq``: the chance that a tenant's op is a dequeue;
* ``prefill``: the items in each tenant's queue before its plan starts.

A dequeue is drawn only while the tenant's queue is non-empty, so no
tenant dequeues an empty queue and the fleet takes no bail.  Every tenant
runs one op per step, so every seed gives the same amount of work: the
seed only changes which ops are enqueues.  This is the generator of
``repro.fleet.runner.fleet_kinds``, copied so that the yardstick stays
where the system under test cannot move it.
"""
import numpy as np


def generate(tenants: int, ops: int, seed: int, traffic: dict) -> np.ndarray:
    """(ops, tenants) uint8 plans, 0 enqueue and 1 dequeue."""
    rng = np.random.default_rng(seed)
    kinds = np.zeros((ops, tenants), dtype=np.uint8)
    length = np.full(tenants, traffic["prefill"], dtype=np.int64)
    for c in range(ops):
        deq = (rng.random(tenants) < traffic["p_deq"]) & (length > 0)
        kinds[c] = deq
        length += np.where(deq, -1, 1)
    return kinds
