"""Host set-up: seconds of the program's ``compile`` spans in this run's
set-up (each chunk length's step lowered and compiled ahead of time, from
the persistent cache when it is warm), from the program's own span record
on the host clock."""
from program_spans import setup_s


def read(ctx):
    return setup_s(ctx, ("compile",))
