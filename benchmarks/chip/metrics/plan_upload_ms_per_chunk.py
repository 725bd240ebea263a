"""Host chunk loop: host milliseconds per chunk of the program's
``plan-upload`` span (the chunk's plans and op indices handed to the
devices, ``device_put``) in the traced window."""
from program_spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "plan-upload")
