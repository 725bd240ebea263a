"""Plain reference of one tenant's queue: per-primitive, one thread.

``reference.<queue>`` holds each queue's algorithm and
:mod:`reference.memory` the memory it runs on; :func:`tenant_counts`
runs one tenant's plan through them from a fresh memory.
"""
import importlib

import numpy as np

from .memory import Memory, SSMem


def tenant_counts(config: dict, prefill: int, kinds, tenant: int) -> np.ndarray:
    """The twelve event counts of one tenant from a fresh memory: queue
    construction, ``prefill`` enqueues, one warm-up enqueue and dequeue,
    then ``kinds`` (0 enqueue, 1 dequeue), as the fleet runs each tenant.
    The allocator area holds prefill + plan length + 16 nodes, the fleet's
    sizing, so no tenant ever refills it."""
    queue_cls = importlib.import_module(f"{__name__}.{config['queue']}").Queue
    mem = Memory(config["platform"])
    alloc = SSMem(mem, prefill + len(kinds) + 16)
    q = queue_cls(mem, alloc)
    for i in range(prefill):
        q.enqueue(("pre", i))
    q.enqueue(("warm", 0))
    q.dequeue()
    for t, k in enumerate(kinds):
        if k:
            q.dequeue()
        else:
            q.enqueue(("fleet", tenant, t))
    return np.array(mem.counts, dtype=np.int64)

