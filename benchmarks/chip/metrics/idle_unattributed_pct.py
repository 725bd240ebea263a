"""Device: the share of the traced window's device idle time in which no
program span is open, neither a runner phase (``chunk-step``, ``poll``,
...) nor a native span of the fleet (``plan-pack``, ``poll-readback``,
``counts-readback``, ...): the idle time nothing in the program names.
Summed over the devices."""
from program_spans import program_intervals
from trace_reduce import device_events, gaps, union


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices:
        return None
    spans = program_intervals(ctx)
    if spans is None:
        return None
    named = union(spans)
    idle = unnamed = 0.0
    for dev in ctx.trace.devices:
        j = 0
        for a, b in gaps(device_events(ctx.trace, dev), *ctx.window):
            idle += b - a
            while j < len(named) and named[j][1] <= a:
                j += 1
            t, k = a, j
            while k < len(named) and named[k][0] < b:
                unnamed += max(0.0, named[k][0] - t)
                t = max(t, named[k][1])
                k += 1
            unnamed += max(0.0, b - t)
    if idle == 0:
        return None
    return 100.0 * unnamed / idle
