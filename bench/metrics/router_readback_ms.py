"""Host time a router dispatch spends reading the committed buffers back
(``router.readback``), median over the dispatches in the window, from
the program's spans."""
from bench import program_spans as PS

LAYER = "ST decode router: serving/st_decode.py"
MOVES = "itl_p50_ms"
UNIT = "ms"


def read(run):
    return PS.router_readback_ms(run, PS.ring())
