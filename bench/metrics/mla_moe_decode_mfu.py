"""Share of the chip's peak bf16 FLOP/s that the MLA + MoE decode step
reaches: the FLOPs its active tokens require
(``costs_mla_moe.decode_work``, the held experts by the load the
program counted), mean over the decode calls made while the trace ran,
over the mean device time of a decode executable call."""
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
    flops = sum(CM.decode_work(cfg, ctx, load)[0] for ctx, load in steps)
    took = sum(e - s for s, e, _ in calls) / 1e9
    return 100.0 * (flops / len(steps)) / (took / len(calls)) \
        / peak["bf16_flops_per_s"]
