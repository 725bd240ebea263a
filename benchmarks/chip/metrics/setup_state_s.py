"""Host set-up: seconds of the program's ``replicate`` span (the template
row tiled over every tenant on the host) plus its ``state-upload`` span
(the state padded and placed on the cell's devices), in this run's
set-up, from the program's own span record on the host clock."""
from program_spans import setup_s


def read(ctx):
    return setup_s(ctx, ("replicate", "state-upload"))
