"""Host chunk loop: host milliseconds per chunk of the program's
``poll-readback`` span (``bail_at`` and ``active`` of every tenant copied
to the host once the step is done, the bailed tenants found) in the
traced window.  The wait for the step is ``poll-wait``, not read here."""
from program_spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "poll-readback")
