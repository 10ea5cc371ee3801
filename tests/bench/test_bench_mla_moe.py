"""The MLA + MoE cell's parts: its configuration and traffic found by name,
its per-layer readers on no trace and on a hand-made one, the device time
by name scope read from a hand-made profiler file, and its operation and
byte counts against hand counts."""
import os

import numpy as np
import pytest

from bench import costs_mla_moe as CM
from bench import scopes
from bench import spec
from bench import trace as T
from bench.harness import Outcome

CELL = "moonlight-16b-a3b.chat-plain"
NEW = ("moe_decode_ms", "mla_decode_ms", "moe_expert_roofline",
       "mla_moe_decode_roofline", "mla_moe_decode_mfu")
MS = 1_000_000  # ns

# Moonlight-16B-A3B's config.json, as published
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
    "moe_intermediate_size": 1408, "n_group": 1, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "first_k_dense_replace": 1, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840, "n_routed_experts": 64}


def test_the_config_is_published_but_for_the_experts_held():
    cell = spec.load_cell(CELL)
    c = cell.config
    assert c["reduced"] == ["n_routed_experts"]
    for k, v in PUBLISHED.items():
        want = 8 if k == "n_routed_experts" else v
        assert c[k] == want, k
    assert c["published_n_routed_experts"] == 64
    assert c["experts_held"] == [0, 8]
    assert c["serving"]["moe_impl"] == "ragged"
    bm = spec.load_benchmark()
    entry = next(x for x in bm["configs"] if x["name"] == cell.config_name)
    assert entry["reduced"] == c["reduced"]


def test_the_program_gets_the_published_block():
    drv = spec.load_driver("serve_open_loop_mla_moe")
    m = drv.model_config(spec.load_cell(CELL).config)
    assert (m.num_layers, m.d_model, m.num_heads, m.vocab_size) == \
        (27, 2048, 16, 163840)
    assert (m.mla.q_lora_rank, m.mla.kv_lora_rank, m.mla.qk_nope_head_dim,
            m.mla.qk_rope_head_dim, m.mla.v_head_dim) == (0, 512, 128, 64, 128)
    assert m.mla.rope_interleave
    mo = m.moe
    assert (mo.num_experts, mo.top_k, mo.expert_ff, mo.shared_ff) == \
        (64, 6, 1408, 2816)
    assert (mo.scoring, mo.score_bias, mo.norm_topk, mo.routed_scale) == \
        ("sigmoid", True, True, 2.446)
    assert mo.held == (0, 8) and m.first_dense_ff == 11264
    assert m.layer_groups().prefix == (("mla", "dense"),)
    assert m.layer_groups().repeats == 26


def test_the_traffic_is_chat_st_on_the_plain_route():
    st = spec.load_cell("granite-3-2b.chat-st").traffic
    plain = spec.load_cell("granite-3-2b.chat-plain").traffic
    mm = spec.load_cell(CELL).traffic
    assert plain["decode_route"] is None and st["decode_route"] == "st"
    assert plain["driver"] == "serve_open_loop"
    assert mm["driver"] == "serve_open_loop_mla_moe"
    assert mm["decode_route"] is None
    for k in ("prompt_lognormal", "prompt_buckets", "output_lognormal",
              "order_seed", "order_block", "check_requests", "trace_s"):
        assert plain[k] == st[k] == mm[k], k
    assert plain["rate_per_s"] == st["rate_per_s"]
    assert os.path.exists(spec.driver_path(mm["driver"]))


def test_the_new_metrics_read_only_the_new_cell():
    names = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(NEW) <= names
    for other in ("granite-3-2b.chat-st", "granite-3-2b.chat-plain"):
        assert not set(NEW) & {m["name"] for m in
                               spec.load_cell(other).per_layer}


def test_route_flips_count_only_clear_margins():
    """Prefill and decode positions apart; a set in another order is the
    same set; a flip under a bf16 step of margin is not counted clear;
    the share is the larger of the two kinds'."""
    drv = spec.load_driver("serve_open_loop_mla_moe")
    # one sequence of a 2-token prompt and 2 decode inputs, 2 MoE layers
    ref = np.array([[[0, 1], [2, 3]], [[4, 5], [6, 7]],
                    [[0, 2], [1, 3]], [[5, 6], [7, 0]]])      # (P, L, k)
    prog = np.array([[[1, 0], [2, 9]], [[4, 9], [6, 7]],
                     [[0, 2], [1, 4]], [[5, 6], [7, 0]]])
    margins = np.array([[0.5, 0.001], [0.5, 0.5], [0.5, 0.5], [0.5, 0.5]])
    flips = drv.route_flips([prog], [ref], [margins], [2])
    assert flips == {"prefill": {"pairs": 4, "differ": 2, "differ_clear": 1},
                     "decode": {"pairs": 4, "differ": 1, "differ_clear": 1}}
    assert drv.flip_share(flips) == 0.25
    # a second sequence adds to each kind
    flips = drv.route_flips([prog, ref], [ref, ref], [margins, margins],
                            [2, 3])
    assert flips["prefill"]["pairs"] == 4 + 6
    assert flips["decode"] == {"pairs": 4 + 2, "differ": 1,
                               "differ_clear": 1}


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def _run(trace=None, record=None):
    return Outcome(end_to_end={}, window_start=0.0, checks=[], attempted=0,
                   failed=0, memory_peak_bytes=0, record=record or {},
                   trace=trace, trace_window=None)


def _hand_run():
    """Two 10 ms decode calls and one 200 ms prefill on one chip, with
    the calls' contexts, held-expert loads and device time by scope."""
    c = spec.load_cell(CELL).config
    modules = {0: [(0, 10 * MS, "jit_decode_sample_step(7)"),
                   (20 * MS, 30 * MS, "jit_decode_sample_step(7)"),
                   (40 * MS, 240 * MS, "jit_prefill_sample_step(2)")]}
    ops = {0: [(0, 10 * MS, "fusion.1"), (20 * MS, 30 * MS, "fusion.1"),
               (40 * MS, 240 * MS, "fusion.2")]}
    tr = T.Trace(ops=ops, modules=modules,
                 spans=[(0, 240 * MS, "ServingEngine.step")])
    load = [[2, 1, 0, 3, 1, 2, 0, 1]] * 26
    record = {"kind": "serve", "config": c, "device_kind": "TPU v5 lite",
              "calls_traced": [["decode", [1000] * 16, load],
                               ["decode", [1001] * 16, load],
                               ["prefill", [16, 2048],
                                [[4000] * 8] * 26]],
              "scope_ns": {
                  "decode": {"calls": 2, "scopes": {
                      "moe.route": 0.4 * MS, "moe.experts": 8 * MS,
                      "moe.combine": 0.6 * MS, "moe.shared": 3 * MS,
                      "mla.attend": 5 * MS, "mla.write": 1 * MS}},
                  "prefill": {"calls": 1, "scopes": {
                      "moe.experts": 60 * MS}}}}
    return _run(tr, record)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_without_a_trace(name):
    reader = spec.load_reader(name)
    assert reader.read(_run()) is None
    assert reader.read(_run(record={"kind": "serve"})) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_trace_without_its_parts(name):
    """A parent's traced run: device calls, but no scopes and no loads."""
    run = _hand_run()
    run.record = {k: v for k, v in run.record.items()
                  if k not in ("scope_ns", "calls_traced")}
    assert spec.load_reader(name).read(run) is None


def test_scope_readers_give_ms_per_decode_call():
    run = _hand_run()
    assert spec.load_reader("moe_decode_ms").read(run) == \
        pytest.approx((0.4 + 8 + 0.6 + 3) / 2)
    assert spec.load_reader("mla_decode_ms").read(run) == \
        pytest.approx((5 + 1) / 2)


def test_roofline_readers_on_a_hand_made_run():
    run = _hand_run()
    c = run.record["config"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    steps = [([1000] * 16, run.record["calls_traced"][0][2]),
             ([1001] * 16, run.record["calls_traced"][1][2])]
    need = sum(max(f / peak["bf16_flops_per_s"],
                   b / peak["hbm_bytes_per_s"])
               for f, b in (CM.decode_work(c, ctx, ld) for ctx, ld in steps))
    want = 100 * (need / 2) / 0.010
    got = spec.load_reader("mla_moe_decode_roofline").read(run)
    assert got == pytest.approx(want)
    flops = sum(CM.decode_work(c, ctx, ld)[0] for ctx, ld in steps) / 2
    assert spec.load_reader("mla_moe_decode_mfu").read(run) == \
        pytest.approx(100 * flops / 0.010 / peak["bf16_flops_per_s"])

    def least(layer):
        f, b = CM.expert_work(c, layer)
        return max(f / peak["bf16_flops_per_s"],
                   b / peak["hbm_bytes_per_s"])
    dec = 26 * least([2, 1, 0, 3, 1, 2, 0, 1])
    pre = 26 * least([4000] * 8)
    assert spec.load_reader("moe_expert_roofline").read(run) == \
        pytest.approx(100 * (2 * dec + pre) / (8e-3 + 60e-3))


# ---------------------------------------------------------------------------
# device time by name scope, from a profiler file
# ---------------------------------------------------------------------------

def test_scope_of_takes_the_first_program_scope():
    assert scopes.scope_of([("tf_op", "jit(decode_sample_step)/while/body/"
                             "moe.experts/ragged_dot_general")]) == \
        "moe.experts"
    assert scopes.scope_of([("long_name", "x"), ("tf_op", "jit(f)/"
                             "mla.attend/dot_general:")]) == "mla.attend"
    assert scopes.scope_of([("tf_op", "jit(f)/st.put.put(0,1,1)/x")]) is None
    assert scopes.scope_of([("tf_op", "jit(f)/remove.x/y")]) is None
    # a source file is no scope
    assert scopes.scope_of([("source", "src/repro/models/moe.py:120")]) is None
    # XLA gives the grouped matmul's kernel no name stack
    assert scopes.scope_of([("tf_op", "ragged-dot-none")],
                           "ragged-dot-none.2") == "moe.experts"
    assert scopes.scope_of([], "fusion.3") is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 11 offset_ps: 20000000000
             duration_ps: 5000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000000 }
    events { metadata_id: 4 offset_ps: 5000000000 duration_ps: 1000000000
             stats { metadata_id: 100
                     str_value: "jit(decode_sample_step)/mla.write/x" } }
    events { metadata_id: 5 offset_ps: 6000000000 duration_ps: 500000000 }
    events { metadata_id: 2 offset_ps: 20000000000
             duration_ps: 4000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[] fusion()"
    stats { metadata_id: 100
            str_value: "jit(decode_sample_step)/while/body/moe.experts/r" }
  } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[] fusion()"
    stats { metadata_id: 100
            str_value: "jit(decode_sample_step)/while/body/mla.attend/d" }
  } }
  event_metadata { key: 3 value { id: 3 name: "%while.1 = () while()"
    stats { metadata_id: 100 str_value: "jit(decode_sample_step)/while" }
  } }
  event_metadata { key: 4 value { id: 4 name: "%copy.1 = bf16[] copy()" } }
  event_metadata { key: 5 value { id: 5
    name: "%ragged-dot-none.1 = bf16[] custom-call()"
    stats { metadata_id: 100 str_value: "ragged-dot-none" } } }
  event_metadata { key: 10 value { id: 10
    name: "jit_decode_sample_step(3)" } }
  event_metadata { key: 11 value { id: 11
    name: "jit_prefill_sample_step(3)" } }
  stat_metadata { key: 100 value { id: 100 name: "tf_op" } }
}
"""


def test_scope_ns_sums_device_time_by_call_and_scope(tmp_path):
    """One decode call holding a moe.experts op (2 ms), a mla.attend op
    (3 ms), a loop (left out), an op whose own stat names mla.write
    (1 ms) and the grouped matmul's kernel, with no name stack (0.5 ms);
    one prefill call holding a mla.attend op (4 ms)."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    got = scopes.scope_ns(str(path), {"decode": "decode_sample_step",
                                      "prefill": "prefill_sample_step"})
    assert got["decode"]["calls"] == 1 and got["prefill"]["calls"] == 1
    assert got["decode"]["scopes"] == pytest.approx(
        {"moe.experts": 2.5 * MS, "mla.attend": 3 * MS, "mla.write": 1 * MS})
    assert got["prefill"]["scopes"] == pytest.approx({"mla.attend": 4 * MS})
    record = {"scope_ns": got}
    assert scopes.per_call_ms(record, "decode", "mla.") == pytest.approx(4)
    assert scopes.per_call_ms(record, "prefill", "moe.") is None


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

C = spec.load_cell(CELL).config
MLA = (2048 * 16 * 192 + 2048 * 576 + 2 * 512 * 16 * 128
       + 16 * 128 * 2048)            # wq, wkv_a, wk_b, wv_b, wo
EXPERT = 3 * 2048 * 1408


def test_mla_and_expert_parameters():
    k = CM.W.dims(C)
    assert CM.mla_params(k) == MLA == 13_762_560
    assert CM.expert_params(k) == EXPERT == 8_650_752
    # MLA 0.74 GB and the held experts 3.60 GB, in bf16
    assert 27 * MLA * 2 == pytest.approx(0.743e9, rel=1e-3)
    assert 26 * 8 * EXPERT * 2 == pytest.approx(3.599e9, rel=1e-3)


def test_expert_work_counts_only_experts_that_got_tokens():
    f, b = CM.expert_work(C, [3, 0, 1, 0, 0, 0, 0, 0])
    assert f == 2 * EXPERT * 4
    assert b == (2 * EXPERT + 2 * 2048 * 4) * 2
    assert CM.expert_work(C, [0] * 8) == (0, 0)


def test_decode_token_flops_by_hand():
    ctx = 700
    attn = 2 * MLA + 2 * 16 * ctx * (512 + 64) + 2 * 16 * ctx * 512
    want = (27 * attn + 2 * 3 * 2048 * 11264
            + 26 * (2 * 2048 * 64 + 2 * 3 * 2048 * 2816)
            + 2 * 2048 * 163840)
    assert CM.decode_token_flops(C, ctx) == want


def test_decode_work_weights_cache_and_held_experts():
    weights = (27 * (MLA + 2 * 2048 + 512) + 3 * 2048 * 11264
               + 26 * (2048 * 64 + 3 * 2048 * 2816) + 2048
               + 2048 * 163840) * 2 + 26 * 64 * 4
    assert CM.decode_work(C, [], []) == (0, weights)
    # 2.46 GB besides the held experts: MLA 0.74, shared experts 0.90,
    # layer 0's FFN 0.14, the output head 0.67 (the embedding is a row
    # lookup)
    assert weights == pytest.approx(2.459e9, rel=1e-3)
    row = 27 * 576 * 2                   # one position's latent, bf16
    load = [[1, 0, 2, 0, 0, 0, 0, 0]] * 26
    f, b = CM.decode_work(C, [100, 300], load)
    assert b == weights + row * (100 + 300) + 26 * 2 * EXPERT * 2
    assert f == CM.decode_token_flops(C, 100) + \
        CM.decode_token_flops(C, 300) + 26 * 2 * EXPERT * 3


# ---------------------------------------------------------------------------
# a whole run at a CPU size
# ---------------------------------------------------------------------------

def _tiny_cell():
    """The cell at a size the CPU runs in seconds: widths, depth,
    vocabulary, experts and slots cut (16 routed experts, 4 held, top-6
    kept), the traffic's lengths and rate scaled to match."""
    import copy
    cell = spec.load_cell(CELL)
    c = cell.config = copy.deepcopy(cell.config)
    c.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=128, moe_intermediate_size=32,
             published_n_routed_experts=16, n_routed_experts=4,
             experts_held=[0, 4], num_hidden_layers=3, vocab_size=512)
    c["serving"].update(batch_slots=4, max_len=64)
    cell.traffic = dict(cell.traffic, rate_per_s=20.0,
                        prompt_lognormal={"median": 10, "sigma": 0.6},
                        prompt_buckets=[8, 16],
                        output_lognormal={"median": 6, "sigma": 1.0,
                                          "min": 2, "max": 16})
    return cell


def _bias_dropped(monkeypatch):
    """The program's router picks by its scores alone."""
    import repro.models.moe as moe_mod
    real = moe_mod.route
    monkeypatch.setattr(moe_mod, "route", lambda mo, x, router, bias=None:
                        real(mo, x, router, None))


def _token_altered(monkeypatch):
    """One slot's token altered where the decode step makes it."""
    import repro.serving.engine as engine_mod
    real = engine_mod.make_decode_sample_step

    def make(cfg, rules, **kw):
        step = real(cfg, rules, **kw)

        def broken(params, batch, cache):
            ids, *rest = step(params, batch, cache)
            return (ids.at[0].set((ids[0] + 1) % cfg.vocab_size), *rest)
        return broken
    monkeypatch.setattr(engine_mod, "make_decode_sample_step", make)


@pytest.mark.parametrize("fault", [None, "bias_dropped", "token_altered"])
def test_a_run_is_correct_only_without_a_fault(fault, cpu_cache,
                                               monkeypatch):
    """The judge reads the expert sets the timed steps chose: a router
    that ignores the selection bias shows in ``route_flip_share`` (the
    logits, judged along the program's own sets, cannot show it), an
    altered token in ``max_logit_gap``; the sound program passes both at
    the cell's limits."""
    import time
    from bench.run import execute
    if fault == "bias_dropped":
        _bias_dropped(monkeypatch)
    if fault == "token_altered":
        _token_altered(monkeypatch)
    line = execute(_tiny_cell(), 2**33 + 5, 1.5, False, require_tpu=False,
                   t_process=time.monotonic())
    checks = line["checks"]
    assert line["attempted"] > 10
    assert line["correct"] == (fault is None), checks
    if fault == "bias_dropped":
        assert checks["route_flip_share"]["value"] > \
            checks["route_flip_share"]["limit"]
    if fault == "token_altered":
        assert checks["max_logit_gap"]["value"] > \
            checks["max_logit_gap"]["limit"]
