"""Host chunk loop: host milliseconds per chunk in the runner's
``chunk-step`` phase (``OpcodeJaxBackend.run_chunk``: the plans packed
and uploaded, the compiled step dispatched), on the host clock, over the
traced window.  The dispatch is asynchronous, so this is host work only,
not the step's device time."""


def read(ctx):
    durations = [end - start for name, start, end in ctx.spans
                 if name == "chunk-step"]
    if not durations:
        return None
    return 1e3 * sum(durations) / len(durations)
