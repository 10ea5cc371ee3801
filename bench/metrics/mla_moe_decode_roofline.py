"""Share of its roofline that the MLA + MoE decode step reaches: the
least time the chip needs for one step's required work
(``costs_mla_moe.decode_work``: every weight it uses once, the held
experts that received tokens, each active slot's latent cache over its
context, the FLOPs of its active tokens in the absorbed form), mean
over the decode calls made while the trace ran, against the mean device
time of a decode executable call in the trace."""
from bench import costs
from bench import costs_mla_moe as CM
from bench import trace as T

LAYER = "model step: train/steps.py prefill and decode executables"
MOVES = "itl_p50_ms"
UNIT = "%"
MARK = "decode_sample_step"


def read(run):
    if run.trace is None or run.record.get("kind") != "serve":
        return None
    steps = [(ctx, load) for k, ctx, load in
             run.record.get("calls_traced") or [] if k == "decode"]
    calls = [c for d in run.trace.devices()
             for c in T.module_calls(run.trace, d, MARK)]
    if not steps or not calls:
        return None
    cfg = run.record["config"]
    peak = costs.peaks(run.record["device_kind"])
    need = sum(costs.roofline_seconds(*CM.decode_work(cfg, ctx, load), peak)
               for ctx, load in steps)
    took = sum(e - s for s, e, _ in calls) / 1e9
    return 100.0 * (need / len(steps)) / (took / len(calls))
