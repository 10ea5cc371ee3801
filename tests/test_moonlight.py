"""Moonlight-16B-A3B (DeepSeek-V3's block) at a reduced size on the CPU:
the program against the plain reference (``bench/reference/mla_moe_lm``)
on the same seeded weights, the V3 router against its formula, the
dropless "ragged" MoE against the "dense" oracle, and the held share of
the experts against the uncut layer."""
import copy
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from repro.configs import get_config  # noqa: E402
from repro.models import (cache_specs, forward, init_params,  # noqa: E402
                          logits_from_hidden, model_specs)
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.moe import moe, moe_specs, route  # noqa: E402
from repro.sharding.rules import make_rules  # noqa: E402

SEED = 2**33 + 7


def tiny_file(dtype="float32", held=(2, 4), layers=4):
    """The benchmark's configuration file at a CPU size: q-LoRA off,
    8 routed experts of which ``held`` ([first, end)) are held, one
    dense layer then MoE layers."""
    from bench import spec
    c = copy.deepcopy(spec.load_cell("moonlight-16b-a3b.chat-plain").config)
    c.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=128, moe_intermediate_size=32,
             published_n_routed_experts=8, n_routed_experts=held[1] - held[0],
             experts_held=list(held), num_experts_per_tok=2,
             num_hidden_layers=layers, vocab_size=512)
    c["serving"].update(batch_slots=4, max_len=64, weights_dtype=dtype,
                        compute_dtype=dtype)
    return c


def program(c):
    from bench import spec
    drv = spec.load_driver("serve_open_loop_mla_moe")
    mcfg = drv.model_config(c)
    return mcfg, drv.make_params(mcfg, c, SEED)


def reference_logits(c, seqs, positions):
    from bench.reference import mla_moe_lm as R
    hid = R.hidden_states(c, SEED, seqs, positions)
    head = np.asarray(R._head_table(R._cfg(c), R.W.seed_key(SEED),
                                    "float32", None))
    with jax.default_matmul_precision("highest"):
        return np.asarray(jnp.asarray(hid) @ jnp.asarray(head).T)


def test_prefill_then_decode_matches_the_reference():
    """Prefill 10 positions into a float32 cache, then decode 6 through
    it, all in float32: every logit within 1e-4 of the reference's full
    forward (what is left is float32 summation order: the absorbed
    decode and the grouped matmul add in another order than the
    reference's expanded attention and one-hot experts)."""
    c = tiny_file()
    mcfg, params = program(c)
    rules = make_rules(mcfg, None, None)
    rng = np.random.RandomState(0)
    B, P, n = 2, 10, 6
    toks = rng.randint(1, c["vocab_size"], (B, P + n)).astype(np.int32)
    cache = init_params(cache_specs(mcfg, B, 32, jnp.float32),
                        jax.random.PRNGKey(0), dtype=None)
    pre = {"tokens": jnp.asarray(toks[:, :P]),
           "positions": jnp.broadcast_to(jnp.arange(P), (B, P))}
    with jax.default_matmul_precision("highest"):
        x, cache, _, _ = forward(mcfg, params, pre, rules=rules, cache=cache,
                                 moe_impl="ragged")
        got = [logits_from_hidden(mcfg, params, x, rules)[:, -1]]
        for t in range(P, P + n - 1):
            b = {"tokens": jnp.asarray(toks[:, t:t + 1]),
                 "positions": jnp.full((B, 1), t, jnp.int32)}
            x, cache, _, _ = forward(mcfg, params, b, rules=rules, cache=cache,
                                     moe_impl="ragged")
            got.append(logits_from_hidden(mcfg, params, x, rules)[:, 0])
    got = np.stack([np.asarray(g) for g in got], 1)       # (B, n, V)
    pos = [np.arange(P - 1, P + n - 1)] * B
    want = reference_logits(c, list(toks[:, :P + n - 1]), pos)
    np.testing.assert_allclose(got.reshape(-1, got.shape[-1]), want,
                               atol=1e-4, rtol=0)


def test_served_tokens_through_the_engine_match_the_reference():
    """Requests through ``ServingEngine`` (slots-wide prefill, the
    decode loop, the rows-only latent write, the "ragged" experts) in
    float32: every served token's logit within 0.02 of the reference's
    best at its position. The engine's latent cache is bfloat16, so the
    cached latents carry a rounding of 2^-9 relative; this size reads
    ~0.004 (the float32 path with a float32 cache agrees to 1e-4, the
    test above)."""
    from bench.reference import mla_moe_lm as R
    from repro.serving import Request, ServingEngine
    c = tiny_file()
    mcfg, params = program(c)
    eng = ServingEngine(mcfg, params, make_rules(mcfg, None, None),
                        batch_slots=4, max_len=64, moe_impl="ragged")
    assert eng.stats()["decode_cache_write"] == "rows"
    rng = np.random.RandomState(1)
    reqs = [Request(prompt=rng.randint(1, 512, L).astype(np.int32),
                    max_new_tokens=m) for L, m in ((8, 9), (8, 4), (16, 7),
                                                   (12, 12), (8, 3))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    seqs = [np.concatenate([r.prompt, r.out_tokens[:-1]]) for r in reqs]
    pos = [np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(
        r.out_tokens)) for r in reqs]
    toks = np.concatenate([r.out_tokens for r in reqs])
    hid = R.hidden_states(c, SEED, seqs, pos)
    assert R.logit_gaps(c, SEED, hid, toks).max() < 0.02
    held = eng.stats()["moe_held_assignments"]
    assert len(held) == 2 and sum(held) > 0


def _v3(mcfg):
    return dataclasses.replace(get_config("moonlight-16b-a3b").reduced(),
                               moe=mcfg)


def test_v3_router_matches_its_formula():
    """sigmoid scores; top-k of scores + bias; weights the chosen scores
    over their sum times the routed scale. One token's bias is set so
    that it picks an expert its scores alone would not."""
    cfg = get_config("moonlight-16b-a3b").reduced()
    mo = dataclasses.replace(cfg.moe, top_k=2)
    rng = np.random.RandomState(3)
    x = rng.randn(5, 16).astype(np.float32)
    w = rng.randn(16, 8).astype(np.float32)
    s = 1 / (1 + np.exp(-(x @ w)))
    bias = np.zeros(8, np.float32)
    third = np.argsort(-s[0])[2]       # token 0's third-best expert
    bias[third] = 1.0
    weights, sel, _ = route(mo, jnp.asarray(x), jnp.asarray(w),
                            jnp.asarray(bias))
    weights, sel = np.asarray(weights), np.asarray(sel)
    for t in range(5):
        want = set(np.argsort(-(s[t] + bias))[:2])
        assert set(sel[t]) == want
        chosen = s[t, sel[t]]
        np.testing.assert_allclose(
            weights[t], chosen / chosen.sum() * mo.routed_scale, rtol=1e-5)
    assert third in sel[0]
    assert third not in set(np.argsort(-s[0])[:2])
    # the bias picks, it does not weigh
    np.testing.assert_allclose(weights[0][list(sel[0]).index(third)],
                               s[0, third] / s[0, sel[0]].sum()
                               * mo.routed_scale, rtol=1e-5)


def test_softmax_router_renormalises_only_when_asked():
    """DeepSeek-V2 and deepseek-moe-16b publish norm_topk_prob false:
    their weights are the softmax scores of the chosen experts."""
    for arch in ("deepseek-moe-16b", "deepseek-v2-236b"):
        assert get_config(arch).moe.norm_topk is False
    mo = get_config("deepseek-moe-16b").reduced().moe
    rng = np.random.RandomState(4)
    x, w = rng.randn(3, 16), rng.randn(16, mo.num_experts)
    p = np.exp(x @ w)
    p /= p.sum(-1, keepdims=True)
    weights, sel, _ = route(mo, jnp.asarray(x, jnp.float32),
                            jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(np.asarray(weights),
                               np.take_along_axis(p, np.asarray(sel), -1),
                               rtol=1e-5)


def _moe_cfg(held=None, top_k=2):
    cfg = get_config("moonlight-16b-a3b").reduced()
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=top_k, held=held))


@pytest.mark.parametrize("routing", ["uniform", "skewed", "idle"])
@pytest.mark.parametrize("T,form", [
    pytest.param(6, "dense", id="6"),
    pytest.param(6, "grouped", id="6-grouped"),
    pytest.param(4096 * 2, "grouped", id="8192"),     # two chunks
])
def test_ragged_matches_the_dense_oracle(routing, T, form, monkeypatch):
    """Both forms of the dropless impl give the oracle's result for
    every token, whether the tokens spread over the held experts, one
    held expert takes most of them (far past any capacity a dropping
    impl would give it), or one held expert takes none; most choices
    fall on the 6 experts not held. Both impls choose the same
    experts. The grouped form runs at a few tokens too, with the dense
    form's limit lowered below them."""
    if form == "grouped" and moe_mod.routed_form(T) == "dense":
        monkeypatch.setattr(moe_mod, "DENSE_TOKENS", 0)
    assert moe_mod.routed_form(T) == form
    cfg = _moe_cfg(held=(2, 2))
    rules = make_rules(cfg, None, None)
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    if routing == "skewed":              # expert 2 tops nearly every token
        params["router_bias"] = jnp.zeros(8).at[2].set(5.0)
    if routing == "idle":                # held expert 3 tops no token
        params["router_bias"] = jnp.zeros(8).at[2].set(0.5).at[3].set(-5.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    f = jax.jit(lambda p, x, impl: moe(cfg, p, x, rules, impl=impl),
                static_argnums=2)
    yd, _, sd = f(params, x, "dense")
    yr, _, sr = f(params, x, "ragged")
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yd), atol=2e-5,
                               rtol=1e-5)
    sel = np.asarray(sr)
    assert sel.shape == (1, T, 2) and (sel == np.asarray(sd)).all()
    if routing == "skewed":
        assert int((sel == 2).sum()) > 0.9 * T
    if routing == "idle":
        assert not (sel == 3).any() and (sel == 2).any()
    assert ((sel < 2) | (sel >= 4)).any()


def test_routed_form_follows_the_token_count():
    """A 16-slot decode step (16 tokens) takes the dense form, a 16 x
    256 prefill the grouped one, and the limit lies between."""
    assert moe_mod.routed_form(16) == "dense"
    assert moe_mod.routed_form(16 * 256) == "grouped"
    assert moe_mod.routed_form(moe_mod.DENSE_TOKENS) == "dense"
    assert moe_mod.routed_form(moe_mod.DENSE_TOKENS + 1) == "grouped"


def test_the_engine_reports_the_routed_form_of_each_step_kind():
    """``stats()`` gives the form of the 16-slot engine's decode shape
    and of each prefill shape it ran: dense for the decode, grouped for
    the 16 x 256 prefill."""
    from repro.serving import Request, ServingEngine
    c = tiny_file()
    mcfg, params = program(c)
    eng = ServingEngine(mcfg, params, make_rules(mcfg, None, None),
                        batch_slots=16, max_len=272, moe_impl="ragged")
    rng = np.random.RandomState(6)
    eng.submit(Request(prompt=rng.randint(1, 512, 256).astype(np.int32),
                       max_new_tokens=2))
    eng.run_until_drained()
    st = eng.stats()
    assert st["moe_impl"] == "ragged" and st["decode_steps"] == 1
    assert st["moe_routed_form"] == {"decode": "dense",
                                     "prefill": {256: "grouped"}}


def test_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: the routed parts of the four
    shares, with the shared experts (every chip computes them) counted
    once, give the uncut layer, in the program and in the reference."""
    cfg = _moe_cfg()
    rules = make_rules(cfg, None, None)
    full = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    full["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                   (8,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    want, _, _ = moe(cfg, full, x, rules, impl="dense")
    parts = []
    for first in (0, 2, 4, 6):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(first, 2)))
        p = dict(full, **{k: full[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe(c, p, x, rules, impl="ragged")[0])
    shared = moe(dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=(0, 0))), dict(full, **{
            k: full[k][:0] for k in ("w_gate", "w_up", "w_down")}),
        x, rules, impl="dense")[0]
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared),
                               np.asarray(want), atol=1e-5)

    # the reference: its layer for each share, everything but the routed
    # experts counted once
    from bench.reference import mla_moe_lm as R
    outs = {}
    for held in ((0, 2), (2, 4), (4, 6), (6, 8), (0, 8), (0, 0)):
        cf = tiny_file(held=held)
        h = jnp.asarray(np.random.RandomState(5).randn(1, 7, 64),
                        jnp.float32)
        with jax.default_matmul_precision("highest"):
            outs[held] = np.asarray(R._layer(
                R._cfg(cf), R.W.seed_key(SEED), 1, True, h,
                jnp.asarray([7]), None)[0])
    shares = sum(outs[h] for h in ((0, 2), (2, 4), (4, 6), (6, 8)))
    np.testing.assert_allclose(shares - 3 * outs[(0, 0)], outs[(0, 8)],
                               atol=1e-5)


def test_reduced_keeps_no_query_latent_and_the_share():
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), moe=dataclasses
                              .replace(get_config("moonlight-16b-a3b").moe,
                                       held=(8, 8)))
    r = cfg.reduced()
    assert r.mla.q_lora_rank == 0 and r.mla.rope_interleave
    assert r.moe.num_experts == 8 and r.moe.held == (1, 1)
    assert get_config("deepseek-v2-236b").reduced().mla.q_lora_rank > 0
    mixer = model_specs(r)["prefix"][0]["mixer"]
    assert "wq" in mixer and "wq_a" not in mixer


def test_decode_steps_report_the_held_load():
    """The MoE sample steps return the (MoE layers, held experts)
    assignments of the batch's live rows and the experts every row
    chose; granite's steps return what they always did."""
    from repro.train.steps import make_decode_sample_step
    cfg = dataclasses.replace(_moe_cfg(held=(0, 3)), num_layers=4)
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    cache = init_params(cache_specs(cfg, 2, 16), jax.random.PRNGKey(1),
                        dtype=None)
    b = {"tokens": jnp.asarray([[1], [2]], jnp.int32),
         "positions": jnp.full((2, 1), 3, jnp.int32)}
    step = make_decode_sample_step(cfg, rules, moe_impl="ragged")
    out = step(params, b, cache)
    assert len(out) == 5
    load, sel = np.asarray(out[3]), np.asarray(out[4])
    assert load.shape == (3, 3) and sel.shape == (2, 3 * 2)
    assert sel.dtype == np.uint8
    sel = sel.reshape(2, 3, 2)                              # (B, L, K)
    held = (sel[..., None] == np.arange(3)).sum(2)          # (B, L, n)
    assert (load == held.sum(0)).all()
    live = step(params, dict(b, live=jnp.asarray([False, True])), cache)
    assert (np.asarray(live[3]) == held[1]).all()
    assert (np.asarray(live[4]).reshape(2, 3, 2) == sel).all()
    g = get_config("granite-3-2b").reduced()
    gp = init_params(model_specs(g), jax.random.PRNGKey(0))
    gc = init_params(cache_specs(g, 2, 16), jax.random.PRNGKey(1),
                     dtype=None)
    assert len(make_decode_sample_step(g, make_rules(g, None, None))(
        gp, b, gc)) == 3


def test_the_engine_keeps_each_requests_routes():
    """After ``keep_routes`` the engine gives, for each request, the
    experts its steps chose at every position it fed (prompt, then the
    decode inputs), as the model's own forward over the sequence
    chooses them, and counts the load of its live slots only."""
    from repro.serving import Request, ServingEngine
    c = tiny_file()
    mcfg, params = program(c)
    rules = make_rules(mcfg, None, None)
    eng = ServingEngine(mcfg, params, rules, batch_slots=4, max_len=64,
                        moe_impl="ragged")
    eng.keep_routes()
    rng = np.random.RandomState(2)
    reqs = [Request(prompt=rng.randint(1, 512, L).astype(np.int32),
                    max_new_tokens=m) for L, m in ((8, 5), (12, 3))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    total = 0
    for r in reqs:
        seq = np.concatenate([r.prompt, r.out_tokens[:-1]])
        got = eng.routes(r)
        assert got.shape == (len(seq), 3, 2)
        with jax.default_matmul_precision("highest"):
            _, _, _, routes = forward(
                mcfg, params, {"tokens": jnp.asarray(seq[None]),
                               "positions": jnp.arange(len(seq))[None]},
                rules=rules, moe_impl="ragged")
        want = np.asarray(routes["sel"])[:, 0].transpose(1, 0, 2)
        assert (np.sort(got, -1) == np.sort(want, -1)).all()
        total += int(((got >= 2) & (got < 4)).sum())
    assert sum(eng.stats()["moe_held_assignments"]) == total
