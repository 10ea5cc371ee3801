"""Share of its roofline that the held experts' grouped matmul reaches
(the ``moe.experts`` scope: three ``ragged_dot`` a MoE layer), over the
traced prefill and decode calls. The least time of a call is the sum
over its MoE layers of the larger of ``costs_mla_moe.expert_work``'s
FLOPs over peak bf16 FLOP/s and bytes over HBM bandwidth, for the load
the program counted in that call: the assignments of the slots that
serve a request only, not of idle decode slots or of a prefill's
padding rows, which the kernel computes all the same; the mean least
time of each kind of call, times the calls of that kind on the device,
over the device time under the scope in those calls."""
from bench import costs
from bench import costs_mla_moe as CM

LAYER = "MoE layer: models/moe.py route, grouped matmul, combine, shared"
MOVES = "itl_p50_ms"
UNIT = "%"


def read(run):
    if run.trace is None or run.record.get("kind") != "serve":
        return None
    sn = run.record.get("scope_ns") or {}
    recorded = run.record.get("calls_traced") or []
    cfg = run.record["config"]
    peak = costs.peaks(run.record["device_kind"])
    need_s = took_ns = 0.0
    for kind in ("prefill", "decode"):
        mine = [load for k, _, load in recorded if k == kind]
        got = sn.get(kind) or {}
        ns = (got.get("scopes") or {}).get("moe.experts", 0.0)
        if not mine or not got.get("calls") or not ns:
            continue
        per_call = sum(costs.roofline_seconds(*CM.expert_work(cfg, layer),
                                              peak)
                       for load in mine for layer in load) / len(mine)
        need_s += per_call * got["calls"]
        took_ns += ns
    if not took_ns:
        return None
    return 100.0 * need_s / (took_ns / 1e9)
