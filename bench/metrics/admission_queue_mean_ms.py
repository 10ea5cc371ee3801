"""Mean of a request's wait in the engine's queue (``engine.queued``,
from ``submit`` to its dequeue) over the requests submitted before the
window's middle, from the program's spans: the serve profiler opens no
earlier than half a second before a request due after the middle. Most
requests wait microseconds; the mean holds the few that wait for a
free slot."""
from bench import program_spans as PS

LAYER = "admission: serving/engine.py"
MOVES = "ttft_p50_ms"
UNIT = "ms"


def read(run):
    return PS.admission_queue_mean_ms(run, PS.ring())
