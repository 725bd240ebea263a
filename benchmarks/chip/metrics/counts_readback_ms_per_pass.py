"""Host chunk loop: host milliseconds per pass of the program's
``counts-readback`` span (every tenant's counts copied to the host, made
int64 and merged with the tenants that finished off the device) in the
traced window.  The wait for the last step is ``counts-wait``."""
from program_spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "counts-readback")
