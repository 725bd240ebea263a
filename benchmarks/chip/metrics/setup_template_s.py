"""Host set-up: seconds of the program's ``template`` span in this run's
set-up (``build_template``: the warmed template harness, its schedules
lowered).  Read from the program's own span record on the host clock,
since the profiler is off during set-up."""
from program_spans import setup_s


def read(ctx):
    return setup_s(ctx, ("template",))
