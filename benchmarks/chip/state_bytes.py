"""Bytes the XLA chunk step has to move at the least, from the fleet's
dimensions alone: the tenants' state read once and written once per
chunk, plus the chunk's plans and op indices.  It is worked out from the
template's ``FleetDims`` and the state layout, never from the compiled
program, so the count is the same whatever implements the step: a kernel
that keeps a block of tenants in fast memory for a whole chunk still
moves at least this.  The step does no floating-point work, so its
roofline is bound by these bytes over the HBM bandwidth.
"""
N_EVENTS = 12          # per-tenant event counters, int32 on the device
N_SCALARS = 11         # head, length, dummy_p, dummy_v, nfree, cursor,
                       # nvfree, vcursor, nlimbo, epoch, opsctr: int32


def state_bytes_per_tenant(dims) -> int:
    """One tenant's state on the device, in bytes."""
    line_planes = 3 * dims.nl                   # cached, finval, everfl
    persisted = dims.nl if dims.needs_persisted else 1
    rings = 2 * 4 * dims.cap                    # ring_p, ring_v
    free = 4 * dims.fcap + 4 * dims.vfcap       # free_p, vfree
    limbo = (4 + 4 + 1) * dims.lcap             # addr, epoch, kind
    scalars = 4 * N_SCALARS + 4 * N_EVENTS + 1 + 4   # + active, bail_at
    slots = 4 * len(dims.slot_attrs)
    return (line_planes + persisted + dims.nvw + rings + free + limbo
            + scalars + slots)


def chunk_bytes(dims, tenants: int, chunk: int) -> int:
    """Least bytes one chunk of ``chunk`` ops over ``tenants`` tenants on
    one device reads and writes: state in and out, one plan byte per
    tenant and op, one int32 op index per op."""
    return 2 * state_bytes_per_tenant(dims) * tenants + tenants * chunk \
        + 4 * chunk
