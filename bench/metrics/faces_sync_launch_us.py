"""Host time of the jitted call inside a ``synchronize`` (``st.launch``,
until it returns), median over the calls in the window, from the
program's spans."""
from bench import program_spans as PS

LAYER = "ST executor host path: core/stream.synchronize, core/backends.run_compiled"
MOVES = "faces_us_per_iter"
UNIT = "us"


def read(run):
    return PS.faces_sync_launch_us(run, PS.ring())
