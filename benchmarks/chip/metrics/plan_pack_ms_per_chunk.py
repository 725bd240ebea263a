"""Host chunk loop: host milliseconds per chunk of the program's
``plan-pack`` span (the chunk's plans padded and transposed, its op
indices made) in the traced window."""
from program_spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "plan-pack")
