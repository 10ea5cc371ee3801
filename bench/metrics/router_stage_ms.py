"""Host time a router dispatch spends staging (``router.stage``: padding,
the payloads' ``device_put``, the counter resets), median over the
dispatches in the window, from the program's spans."""
from bench import program_spans as PS

LAYER = "ST decode router: serving/st_decode.py"
MOVES = "itl_p50_ms"
UNIT = "ms"


def read(run):
    return PS.router_stage_ms(run, PS.ring())
