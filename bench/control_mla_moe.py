"""Readings that set the limits of ``correct`` for an MLA + MoE serving
cell (driver ``serve_open_loop_mla_moe``): the program's over many
seeds, and the control's, in one process on the chip.

    python bench/control_mla_moe.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell's timed path for a short window at the
cell's own load and size, and prints one JSON line with the numbers the
run compares (``program``) beside the same numbers for the control
(``control``): the float32 reference computed in fp8 (e4m3: matrices
per output channel, matrix inputs per token; the router's logits in
float32), one step below the configuration's bfloat16. The control
chooses its own experts at every position; the float32 reference then
runs along those choices, as it runs along the program's, and the token
the control puts first at each judged position is read against it as a
served token is (``max_logit_gap``), and the control's expert sets
against the reference's own choices as the program's are
(``route_flip_share``).

The benchmark's own runs never run this; ``bench/control.py`` does the
same for the other cells.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402
from bench.run import limit_visible_chips, require_chips  # noqa: E402


def readings(cell, seed, seconds):
    from bench.reference import mla_moe_lm as R
    drv = spec.load_driver("serve_open_loop_mla_moe")
    c = cell.config
    eng, params = drv.build(cell, seed)
    eng.keep_routes()
    arrivals = drv.schedule(cell.traffic, seconds, seed, c["vocab_size"])
    _t0, _t1, tracked, _late = drv.run_window(eng, arrivals, seconds)
    picked = drv.pick_requests(tracked, seed, cell.traffic["check_requests"])
    routes = [eng.routes(t.req) for t in picked]
    del eng, params
    gc.collect()
    seqs, pos, toks = drv.served_sequences(picked)
    prompt_lens = [len(t.arr.prompt) for t in picked]
    t0 = time.monotonic()
    gap, share, flips = drv.readings(c, seed, seqs, pos, toks, routes,
                                     prompt_lens)
    t1 = time.monotonic()
    # the control: the reference in fp8 over the same sequences, its own
    # expert choices at every position and its first token where the
    # program served one, judged as the program is
    hid_q, q_routes, _ = R.hidden_states(c, seed, seqs, pos, quant="fp8",
                                         pad_to=c["serving"]["max_len"],
                                         routes=True)
    first = R.argmax_tokens(c, seed, hid_q, quant="fp8")
    q_gap, q_share, q_flips = drv.readings(c, seed, seqs, pos, first,
                                           q_routes, prompt_lens)
    t2 = time.monotonic()
    return ({"max_logit_gap": gap, "route_flip_share": share},
            {"max_logit_gap": q_gap, "route_flip_share": q_share},
            {"requests": len(picked), "tokens": int(len(toks)),
             "control_differs": int((first != toks).sum()),
             "program_routes": flips, "control_routes": q_flips,
             "judge_s": {"program": t1 - t0, "control": t2 - t1}})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    if cell.traffic["driver"] != "serve_open_loop_mla_moe":
        raise SystemExit("bench: control_mla_moe reads serve_open_loop_mla_moe "
                         "cells; bench/control.py reads the others")
    limit_visible_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    require_chips(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        prog, ctrl, info = readings(cell, seed, args.seconds)
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          **info}), flush=True)


if __name__ == "__main__":
    main()
