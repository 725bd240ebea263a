"""XLA chunk step: device nanoseconds of the compiled chunk program per
simulated queue-op on that device, averaged over the devices.  The
program is found by its module name in the trace; where it is not found,
or not once per chunk of every traced pass, nothing is read."""
from trace_reduce import chunk_program_ns


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices:
        return None
    runs = ctx.passes * len(ctx.chunks)
    times = [chunk_program_ns(ctx.trace, dev, *ctx.window, runs)
             for dev in ctx.trace.devices]
    if None in times:
        return None
    ops = ctx.passes * ctx.tenants_per_device * ctx.ops
    return sum(times) / len(times) / ops
