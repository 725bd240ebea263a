"""XLA chunk step: device nanoseconds of the op programs' opcode rows per
simulated queue-op on that device, averaged over the devices: the own
time of the ops under a ``row-<opcode>`` named scope (one ``switch``
branch of the row loop each) in runs of the chunk program."""
from step_scopes import ns_per_op

ROWS = ("row-class-p", "row-class-v", "row-st-inval", "row-st-everfl",
        "row-recache", "row-limbo", "row-slot", "row-pdiscard", "row-padd")


def read(ctx):
    return ns_per_op(ctx, ROWS)
