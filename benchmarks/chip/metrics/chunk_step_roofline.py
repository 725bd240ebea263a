"""XLA chunk step: share of its HBM roofline.  The least time of a chunk
is the bytes it has to move (``state_bytes.chunk_bytes``: the tenants'
state read and written once, plus the plans) over the device's published
HBM bandwidth; the share is the least time of all traced chunks over
their device time, averaged over the devices.  The step does no
floating-point work, so bytes bound it."""
from state_bytes import chunk_bytes
from trace_reduce import chunk_program_ns


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.trace.devices \
            or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    least_ns = 1e9 * ctx.passes * sum(
        chunk_bytes(ctx.dims, ctx.tenants_per_device, c) for c in ctx.chunks
    ) / ctx.peaks["hbm_bytes_per_s"]
    runs = ctx.passes * len(ctx.chunks)
    times = [chunk_program_ns(ctx.trace, dev, *ctx.window, runs)
             for dev in ctx.trace.devices]
    if None in times or not all(times):
        return None
    return sum(100.0 * least_ns / t for t in times) / len(times)
