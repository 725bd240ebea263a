"""XLA chunk step: device nanoseconds of the op programs' bail guards
(``tail_persisted``, ``slot_nonnull``) per simulated queue-op on that
device, averaged over the devices: the own time of the ops under the
``guards`` named scope in runs of the chunk program."""
from step_scopes import ns_per_op


def read(ctx):
    return ns_per_op(ctx, ("guards",))
