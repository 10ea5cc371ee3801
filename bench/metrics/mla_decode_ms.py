"""Device time per decode call of the operations under the program's
``mla.*`` name scopes (``mla.attend``, ``mla.write``): the latent
attention's share of a decode call, its cache read and row write
included, read by ``bench.scopes`` from the traced run's profiler
file."""
from bench import scopes

LAYER = "latent attention: models/mla.py absorbed decode and row write"
MOVES = "itl_p50_ms"
UNIT = "ms"


def read(run):
    if run.trace is None or run.record.get("kind") != "serve":
        return None
    return scopes.per_call_ms(run.record, "decode", "mla.")
