"""Host time of the serve program's ``synchronize`` inside a router
dispatch (``st.synchronize`` under ``router.dispatch``), median over
the dispatches in the window, from the program's spans."""
from bench import program_spans as PS

LAYER = "ST decode router: serving/st_decode.py"
MOVES = "itl_p50_ms"
UNIT = "ms"


def read(run):
    return PS.router_sync_ms(run, PS.ring())
