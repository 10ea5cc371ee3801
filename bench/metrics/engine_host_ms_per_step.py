"""The serving engine's own host time of a decode step: ``engine.step``
less ``engine.ids`` (the wait for the device) and ``router.dispatch``,
median over the steps in the window that admit nothing, from the
program's spans."""
from bench import program_spans as PS

LAYER = "serving engine host path: serving/engine.py step"
MOVES = "itl_p50_ms"
UNIT = "ms"


def read(run):
    return PS.engine_host_ms_per_step(run, PS.ring())
