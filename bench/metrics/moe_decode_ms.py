"""Device time per decode call of the operations under the program's
``moe.*`` name scopes (``moe.route``, ``moe.experts``, ``moe.combine``,
``moe.shared``): the MoE layers' share of a decode call, read by
``bench.scopes`` from the traced run's profiler file."""
from bench import scopes

LAYER = "MoE layer: models/moe.py route, grouped matmul, combine, shared"
MOVES = "itl_p50_ms"
UNIT = "ms"


def read(run):
    if run.trace is None or run.record.get("kind") != "serve":
        return None
    return scopes.per_call_ms(run.record, "decode", "moe.")
