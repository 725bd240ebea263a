"""XLA chunk step: device nanoseconds of the opcode rows that keep the
volatile durability hints (the persisted-node set and the pending-flush
slot) per simulated queue-op on that device, averaged over the devices:
the own time of the ops under ``row-slot``, ``row-pdiscard`` and
``row-padd`` in runs of the chunk program."""
from step_scopes import ns_per_op


def read(ctx):
    return ns_per_op(ctx, ("row-slot", "row-pdiscard", "row-padd"))
