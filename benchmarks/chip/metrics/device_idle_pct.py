"""Device: the share of the traced window in which no operation ran on
the device, averaged over the devices: 100 x (1 - union of the device's
op intervals / window)."""
from trace_reduce import busy_ns, device_events


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.window
    busy = [busy_ns(device_events(ctx.trace, d), lo, hi)
            for d in ctx.trace.devices]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
