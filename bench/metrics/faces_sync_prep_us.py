"""Host time a ``synchronize`` call spends before its launch, median over
the calls in the window: ``st.schedule`` (the queue's cache key and the
scheduled-program lookup) and ``st.lookup`` (the executor's cache key,
``prog.key()``, and its lookup), from the program's spans."""
from bench import program_spans as PS

LAYER = "ST executor host path: core/stream.synchronize, core/backends.run_compiled"
MOVES = "faces_us_per_iter"
UNIT = "us"


def read(run):
    return PS.faces_sync_prep_us(run, PS.ring())
