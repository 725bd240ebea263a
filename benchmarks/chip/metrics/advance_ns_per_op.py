"""XLA chunk step: device nanoseconds of the epoch advance per simulated
queue-op on that device, averaged over the devices.  The advance is the
ops the compiled step runs under its ``epoch-advance`` named scope (the
batch-level ``lax.cond`` and its branch), found by name in the optimized
HLO of the program's newest compiled chunk step; their own time is taken
from the op events inside runs of the chunk program in the window."""
from program_spans import chunk_ops, op_scopes
from trace_reduce import self_times


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices:
        return None
    scopes = op_scopes()
    if scopes is None:
        return None
    advance = {op for op, under in scopes.items() if "epoch-advance" in under}
    times = []
    for dev in ctx.trace.devices:
        ops = chunk_ops(ctx.trace, dev, *ctx.window)
        times.append(sum(own for name, _, own in self_times(ops)
                         if name in advance))
    if not any(times):
        return None
    ops = ctx.passes * ctx.tenants_per_device * ctx.ops
    return sum(times) / len(times) / ops
