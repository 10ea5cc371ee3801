"""Open-loop chat driver of the serving engine on an MLA + MoE decoder
(DeepSeek-V3's block) that holds one chip's share of the routed experts.

The traffic, the window and the latencies are ``serve_open_loop``'s
(imported: ``schedule``, ``warm_up``, ``run_window``, ``latencies``,
``pick_requests``, ``served_sequences``, ``trace_start``). What is this
configuration's own:

  * the program's model: the registered architecture at the file's
    sizes, holding the routed experts ``experts_held`` of
    ``published_n_routed_experts`` (``MoEConfig.held``), its MoE layers
    run by the file's ``moe_impl``;
  * the weights, made on the device from the seed leaf by leaf as
    ``bench.weights_mla_moe`` names them;
  * the judge, ``bench.reference.mla_moe_lm``, fed the expert sets the
    timed prefill and decode steps chose for the judged requests
    (``ServingEngine.routes``): the reference runs along those sets, so
    ``max_logit_gap`` (as ``serve_open_loop`` reads it) weighs the
    served tokens by the numbers alone, not by a near-tie that sent a
    token to another expert; and ``route_flip_share`` holds the choices
    themselves: of the (position, MoE layer) pairs fed by the prefill,
    and apart of those fed by the decode steps, the larger share where
    the program chose another set than the reference would there
    although the reference's k-th choice value leads its (k+1)-th by
    more than one bf16 step of those values;
  * in a traced run, each prefill and decode call's held-expert load
    (the program's counter) and the device time under the program's
    ``moe.*`` and ``mla.*`` name scopes (``bench.scopes``).

Only the plain decode route: the traffic's ``decode_route`` is null.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import numpy as np

from bench import scopes as SC
from bench import stats
from bench import trace as T
from bench import weights_mla_moe as W
from bench.drivers.serve_open_loop import (  # noqa: F401  (reused by import)
    NOTHING_SERVED, latencies, pick_requests, run_window, schedule,
    served_sequences, trace_start, warm_up)
from bench.harness import Check, Outcome, memory_peak_bytes

SPANS = ("ServingEngine.step", "idle_wait", "prefill_call", "decode_call")
CALLS = {"prefill": "prefill_sample_step", "decode": "decode_sample_step"}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(c: dict):
    """The program's model configuration at the file's sizes and share."""
    from repro.configs import get_config
    base = get_config(c["serving"]["arch"])
    if (c["topk_method"], c["n_group"], c["topk_group"]) != \
            ("noaux_tc", 1, 1) or c["first_k_dense_replace"] != 1:
        raise ValueError("the block routes top-k over one group after one "
                         "dense layer")
    ff = c["moe_intermediate_size"]
    moe = dataclasses.replace(
        base.moe, num_experts=c["published_n_routed_experts"],
        top_k=c["num_experts_per_tok"], expert_ff=ff,
        num_shared=c["n_shared_experts"],
        shared_ff=ff * c["n_shared_experts"], scoring=c["scoring_func"],
        norm_topk=bool(c["norm_topk_prob"]),
        routed_scale=float(c["routed_scaling_factor"]), held=W.held(c))
    mla = dataclasses.replace(
        base.mla, kv_lora_rank=c["kv_lora_rank"],
        q_lora_rank=c["q_lora_rank"] or 0,
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"])
    return dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=ff,
        first_dense_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), moe=moe, mla=mla,
        compute_dtype=c["serving"]["compute_dtype"])


def make_params(mcfg, c: dict, seed: int):
    """The program's parameter tree, made on the device from the seed in
    one jitted call, leaf by leaf as ``bench.weights_mla_moe`` names them:
    the ``prefix`` blocks are the first layers, the scanned ``unit`` (one
    block) the rest. Every leaf in the configuration's dtype but the
    selection bias, which the published model keeps in float32."""
    import jax
    import jax.numpy as jnp
    from repro.models import model_specs
    from repro.models.params import is_spec

    specs = model_specs(mcfg)
    groups = mcfg.layer_groups()
    if len(groups.unit) > 1:
        raise ValueError("one block kind repeats after the prefix")
    dt = jnp.dtype(c["serving"]["weights_dtype"])
    k = W.dims(c)
    first, count = W.held(c)
    dense = {**W.mixer_shapes(k), **W.dense_shapes(k)}
    moe = {**W.mixer_shapes(k), **W.moe_shapes(k)}

    def one_layer(key, name, layer, shape, routed):
        if routed and name in W.ROUTED:
            v = W.experts(key, name, layer, first, count, shape)
        else:
            v = W.leaf(key, name, layer, shape)
        return v.astype(jnp.float32 if name == "ffn.router_bias" else dt)

    def gen(key):
        def one(path, spec):
            keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
            names = [x for x in keys if isinstance(x, str)]
            name = ".".join(names[1:])
            if names[0] == "prefix":
                i = keys[1]
                routed = groups.prefix[i][1] == "moe"
                out = one_layer(key, name, i, (moe if routed else dense)[name],
                                routed)
            elif names[0] == "unit":
                out = jax.lax.map(
                    lambda i: one_layer(key, name, i, moe[name], True),
                    len(groups.prefix) + jnp.arange(groups.repeats))
            elif names == ["embed", "tok"]:
                out = W.table(key, "embed.tok", k["vocab"], k["d"],
                              W.EMBED_STD).astype(dt)
            elif names == ["embed", "unembed"]:
                out = W.table(key, "embed.unembed", k["vocab"], k["d"],
                              1.0 / np.sqrt(k["d"])).astype(dt).T
            elif names == ["final_norm", "scale"]:
                out = W.leaf(key, "final_norm.scale", 0, (k["d"],)).astype(dt)
            else:
                raise ValueError(f"not an MLA + MoE decoder leaf: {keys}")
            if tuple(out.shape) != tuple(spec.shape):
                raise ValueError(f"leaf {keys}: made {out.shape}, the "
                                 f"program has {spec.shape}")
            return out
        return jax.tree_util.tree_map_with_path(one, specs, is_leaf=is_spec)

    return jax.jit(gen)(W.seed_key(seed))


def make_engine(cell, mcfg, params):
    from repro.serving import ServingEngine
    from repro.sharding.rules import make_rules

    s = cell.config["serving"]
    if cell.traffic.get("decode_route") is not None:
        raise ValueError("this driver serves the plain decode route only")
    return ServingEngine(mcfg, params, make_rules(mcfg, None, None),
                         batch_slots=s["batch_slots"], max_len=s["max_len"],
                         moe_impl=s["moe_impl"])


def build(cell, seed):
    """Weights, engine and warm-up: the set-up of a run."""
    c = cell.config
    mcfg = model_config(c)
    params = make_params(mcfg, c, seed)
    eng = make_engine(cell, mcfg, params)
    warm_up(eng, cell.traffic, np.random.default_rng(seed + 2),
            c["vocab_size"])
    return eng, params


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def route_flips(prog, ref, margins, prompt_lens) -> dict:
    """For the prompt's positions (``prefill``) and the decode steps'
    inputs (``decode``) apart: the (position, MoE layer) pairs, those
    whose chosen sets differ between ``prog`` and ``ref`` (per sequence,
    (positions, MoE layers, k)), and those of them where the reference's
    k-th choice value leads its (k+1)-th by more than one bf16 step
    (2^-7 of the values, about 0.004 to 0.008 here), where rounding
    alone cannot swap them."""
    out = {kind: {"pairs": 0, "differ": 0, "differ_clear": 0}
           for kind in ("prefill", "decode")}
    for p, r, m, n in zip(prog, ref, margins, prompt_lens):
        differ = np.any(np.sort(p, -1) != np.sort(r, -1), -1)
        clear = differ & (m > 2.0 ** -7)
        for kind, part in (("prefill", slice(0, n)),
                           ("decode", slice(n, None))):
            o = out[kind]
            o["pairs"] += int(differ[part].size)
            o["differ"] += int(differ[part].sum())
            o["differ_clear"] += int(clear[part].sum())
    return out


def flip_share(flips: dict) -> float:
    """The larger of the prefill's and the decode's clear-flip shares."""
    return max(f["differ_clear"] / max(f["pairs"], 1)
               for f in flips.values())


def readings(c: dict, seed: int, seqs, pos, toks, routes,
             prompt_lens) -> tuple:
    """(max_logit_gap, route_flip_share, flips) of ``toks`` served at
    ``pos`` of ``seqs`` by a forward that chose ``routes``, against the
    float32 reference run along those routes."""
    from bench.reference import mla_moe_lm
    hid, ref_sel, margins = mla_moe_lm.hidden_states(
        c, seed, seqs, pos, pad_to=c["serving"]["max_len"], routes=True,
        follow=routes)
    gaps = mla_moe_lm.logit_gaps(c, seed, hid, toks)
    flips = route_flips(routes, ref_sel, margins, prompt_lens)
    return float(gaps.max()), flip_share(flips), flips


def judge(c: dict, seed: int, picked, routes) -> tuple:
    """(checks, notes): ``max_logit_gap`` and ``route_flip_share`` of
    the ``picked`` requests, whose positions chose ``routes``."""
    lim = c["check_limits"]
    if not picked:      # nothing finished: nothing served can be trusted
        return [Check("max_logit_gap", NOTHING_SERVED, lim["max_logit_gap"]),
                Check("route_flip_share", NOTHING_SERVED,
                      lim["route_flip_share"])], []
    seqs, pos, toks = served_sequences(picked)
    gap, share, flips = readings(c, seed, seqs, pos, toks, routes,
                                 [len(t.arr.prompt) for t in picked])
    notes = [f"routes, {kind}: {f['differ']} of {f['pairs']} (position, "
             f"layer) sets differ from the reference's, "
             f"{f['differ_clear']} beyond a bf16 step of their margin"
             for kind, f in flips.items()]
    return [Check("max_logit_gap", gap, lim["max_logit_gap"]),
            Check("route_flip_share", share, lim["route_flip_share"])], notes


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell, seed, seconds, trace_dir, compiles) -> Outcome:
    import jax

    c, tr = cell.config, cell.traffic
    eng, _params = build(cell, seed)
    eng.keep_routes()
    devices = [jax.devices()[0]]
    arrivals = schedule(tr, seconds, seed, c["vocab_size"])
    tracing = trace_dir is not None
    calls: List[list] = []        # [kind, contexts, load] per traced call
    recording = [False]
    if tracing:
        real_prefill, real_decode = eng._prefill_sample, eng._decode_sample

        def prefill(params_, batch):
            with jax.profiler.TraceAnnotation("prefill_call"):
                out = real_prefill(params_, batch)
            if recording[0]:        # (ids, cache, load, sel)
                calls.append(["prefill", list(batch["tokens"].shape),
                              out[2]])
            return out

        def decode(params_, batch, cache):
            ctx = [int(eng.slot_pos[i]) + 1 for i, r in
                   enumerate(eng.slot_req) if r]
            with jax.profiler.TraceAnnotation("decode_call"):
                out = real_decode(params_, batch, cache)
            if recording[0]:        # (ids, hid, cache, load, sel)
                calls.append(["decode", ctx, out[3]])
            return out
        eng._prefill_sample, eng._decode_sample = prefill, decode

    def on_profile(on):
        if on:
            jax.profiler.start_trace(trace_dir)
        else:
            jax.profiler.stop_trace()
        recording[0] = on

    c0 = compiles.count
    d0 = (eng.decode_steps, eng.prefill_dispatches)
    t0, t1, tracked, lateness = run_window(
        eng, arrivals, seconds, spans=tracing,
        on_profile=on_profile if tracing else None,
        profile_at=trace_start(arrivals, seconds) if tracing else None,
        profile_s=tr["trace_s"])
    in_window = compiles.count - c0
    decode_steps = eng.decode_steps - d0[0]
    prefills = eng.prefill_dispatches - d0[1]
    peak = memory_peak_bytes(devices)
    lat = latencies(tracked, t1)
    held_load = eng.stats()["moe_held_assignments"]

    picked = pick_requests(tracked, seed, tr["check_requests"])
    routes = [eng.routes(t.req) for t in picked]
    # free the program's state before the reference takes the chip
    if tracing:
        del real_prefill, real_decode, prefill, decode
    del eng, _params
    gc.collect()
    t_check = time.monotonic()
    checks, check_notes = judge(c, seed, picked, routes)
    check_s = time.monotonic() - t_check

    e2e = {"ttft_p50_ms": stats.percentile(lat["ttft"], 50) * 1e3,
           "itl_p50_ms": stats.percentile(lat["gaps"], 50) * 1e3}
    record = {"kind": "serve", "config": c, "requests": len(tracked),
              "queue_wait_s": lat["queue_wait"], "decode_steps": decode_steps,
              "prefill_dispatches": prefills,
              "moe_held_assignments": held_load,
              "calls_traced": [[kind, ctx, np.asarray(load).tolist()]
                               for kind, ctx, load in calls],
              "device_kind": devices[0].device_kind}
    served = sum(len(t.req.out_tokens) for t in picked)
    out = Outcome(
        end_to_end=e2e, window_start=t0, checks=checks,
        attempted=len(tracked), failed=lat["failed"],
        memory_peak_bytes=peak, record=record,
        notes=[f"window: {len(tracked)} requests due, {len(lat['gaps'])} "
               f"inter-token gaps, {decode_steps} decode steps, {prefills} "
               f"prefills",
               f"submission lateness: max {max(lateness, default=0.0):.6f} "
               f"s, p50 {np.median(lateness) if lateness else 0.0:.6f} s",
               f"ttft p75 {stats.percentile(lat['ttft'], 75) * 1e3:.3f} ms, "
               f"p90 {stats.percentile(lat['ttft'], 90) * 1e3:.3f} ms; "
               f"itl p90 {stats.percentile(lat['gaps'], 90) * 1e3:.3f} ms, "
               f"p95 {stats.percentile(lat['gaps'], 95) * 1e3:.3f} ms, "
               f"p99 {stats.percentile(lat['gaps'], 99) * 1e3:.3f} ms",
               f"compilations inside the window: {in_window}",
               f"held-expert assignments over the run: {held_load}",
               f"reference judged {len(picked)} requests, {served} served "
               f"tokens in {check_s:.3f} s"] + check_notes)
    if tracing:
        path = T.newest_xplane(trace_dir)
        trc = T.from_xplane(path, SPANS)
        out.trace = trc
        out.trace_window = trc.window(("ServingEngine.step", "idle_wait"))
        record["scope_ns"] = SC.scope_ns(path, CALLS)
        out.notes.append(f"device ns by call and scope: "
                         f"{record['scope_ns']}")
    return out
