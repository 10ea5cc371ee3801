"""Model-math invariants: MLA absorbed==expanded, MoE gshard==dense oracle,
fused loss==unfused, rope properties, the decode step's in-place KV row
write == the where-mask write, sharding-rule logic."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # degrade to example-based sweeps
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import SHAPES, get_config
from repro.models import (forward, init_params, lm_loss, logits_from_hidden,
                          model_specs, cache_specs)
from repro.models.layers import apply_rope
from repro.models.model import decode_cache_write, lm_loss_fused
from repro.models.moe import moe_dense, moe_gshard, moe_specs
from repro.models.params import is_spec
from repro.sharding.rules import ShardingRules, make_rules
from repro.train.steps import make_decode_sample_step


def test_mla_absorbed_decode_matches_expand():
    """Decode via the absorbed (latent) path == full expand path."""
    cfg = get_config("deepseek-v2-236b").reduced()
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size,
                                                        (B, S + 1)))
    # path A: prefill 0..S then decode token S via absorbed attention
    cache = init_params(cache_specs(cfg, B, S + 1), jax.random.PRNGKey(1),
                        dtype=None)
    pre = {"tokens": toks[:, :S],
           "positions": jnp.broadcast_to(jnp.arange(S), (B, S))}
    _, cache, _, _ = forward(cfg, params, pre, rules=rules, cache=cache,
                             moe_impl="dense")
    dec = {"tokens": toks[:, S:S + 1],
           "positions": jnp.full((B, 1), S, jnp.int32)}
    xd, _, _, _ = forward(cfg, params, dec, rules=rules, cache=cache,
                          moe_impl="dense")
    la = logits_from_hidden(cfg, params, xd, rules, last_only=True)
    # path B: full forward over S+1 tokens (expand path), take last position
    full = {"tokens": toks,
            "positions": jnp.broadcast_to(jnp.arange(S + 1), (B, S + 1))}
    xf, _, _, _ = forward(cfg, params, full, rules=rules, moe_impl="dense")
    lb = logits_from_hidden(cfg, params, xf, rules)[:, -1:, :]
    np.testing.assert_allclose(np.asarray(la, np.float32),
                               np.asarray(lb, np.float32), atol=3e-2)


def test_decode_matches_full_forward_dense():
    """Generic cache correctness: step-by-step decode == full forward."""
    cfg = get_config("granite-3-2b").reduced()
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 8
    toks = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (B, S)))
    full = {"tokens": toks,
            "positions": jnp.broadcast_to(jnp.arange(S), (B, S))}
    xf, _, _, _ = forward(cfg, params, full, rules=rules, moe_impl="dense")
    lf = logits_from_hidden(cfg, params, xf, rules)

    cache = init_params(cache_specs(cfg, B, S), jax.random.PRNGKey(1),
                        dtype=None)
    logits_steps = []
    for t in range(S):
        b = {"tokens": toks[:, t:t + 1],
             "positions": jnp.full((B, 1), t, jnp.int32)}
        xd, cache, _, _ = forward(cfg, params, b, rules=rules, cache=cache,
                                  moe_impl="dense")
        logits_steps.append(
            logits_from_hidden(cfg, params, xd, rules, last_only=True))
    ld = jnp.concatenate(logits_steps, axis=1)
    np.testing.assert_allclose(np.asarray(ld, np.float32),
                               np.asarray(lf, np.float32), atol=3e-2)


def test_rwkv_decode_matches_full_forward():
    cfg = get_config("rwkv6-1.6b").reduced()
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 1, 6
    toks = jnp.asarray(np.random.RandomState(2).randint(
        0, cfg.vocab_size, (B, S)))
    full = {"tokens": toks,
            "positions": jnp.broadcast_to(jnp.arange(S), (B, S))}
    xf, _, _, _ = forward(cfg, params, full, rules=rules, moe_impl="dense")
    lf = logits_from_hidden(cfg, params, xf, rules)

    cache = init_params(cache_specs(cfg, B, S), jax.random.PRNGKey(1),
                        dtype=None)
    outs = []
    for t in range(S):
        b = {"tokens": toks[:, t:t + 1],
             "positions": jnp.full((B, 1), t, jnp.int32)}
        xd, cache, _, _ = forward(cfg, params, b, rules=rules, cache=cache,
                                  moe_impl="dense")
        outs.append(logits_from_hidden(cfg, params, xd, rules,
                                       last_only=True))
    ld = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(ld, np.float32),
                               np.asarray(lf, np.float32), atol=3e-2)


def test_moe_gshard_matches_dense_when_capacity_ample():
    cfg = dataclasses.replace(
        get_config("deepseek-moe-16b").reduced())
    # huge capacity factor -> no drops -> gshard == dense
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    rules = make_rules(cfg, None, None)
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32) * 0.3
    yd, auxd = moe_dense(cfg, params, x, rules)
    yg, auxg = moe_gshard(cfg, params, x, rules)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yd), atol=2e-2)
    assert abs(float(auxd) - float(auxg)) < 1e-4


def test_fused_loss_matches_unfused():
    cfg = get_config("qwen3-32b").reduced()
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model),
                          jnp.float32)
    t = jnp.ones((B, S), jnp.int32)
    l1 = lm_loss(cfg, logits_from_hidden(cfg, params, x, rules), t, rules)
    l2 = lm_loss_fused(cfg, params, x, t, rules, chunk=8)
    assert abs(float(l1) - float(l2)) < 1e-3


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), shift=st.integers(1, 64))
def test_rope_relative_property(seed, shift):
    """RoPE property: <rope(q,p), rope(k,p')> depends only on p - p'."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, 1, 1, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 1, 1, 64).astype(np.float32))
    p0 = jnp.asarray([[3]]); p1 = jnp.asarray([[10]])
    d0 = jnp.sum(apply_rope(q, p0, 1e4) * apply_rope(k, p1, 1e4))
    d1 = jnp.sum(apply_rope(q, p0 + shift, 1e4)
                 * apply_rope(k, p1 + shift, 1e4))
    np.testing.assert_allclose(float(d0), float(d1), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode KV write paths: rows (in place after the layer scan) vs mask
# ---------------------------------------------------------------------------

def _kv_seq_sharded_rules(rules, size=2):
    """``rules`` with ``kv_seq`` on a mesh axis of ``size``: what the
    decode step's write-path selection sees on a sharded cache. No
    constraint is applied (``enabled=False``), so it runs on one CPU."""
    return ShardingRules(mesh=AbstractMesh((size,), ("model",)),
                         map=dict(rules.map, kv_seq="model"), enabled=False)


def _write_path_cfg(name, attn_impl):
    cfg = dataclasses.replace(get_config(name).reduced(), attn_impl=attn_impl)
    if name == "deepseek-moe-16b":      # GQA MoE, a prefix and a unit
        cfg = dataclasses.replace(cfg, num_layers=4, num_kv_heads=2)
    if name == "jamba-1.5-large-398b":  # attn + mamba in one scanned unit
        cfg = dataclasses.replace(cfg, num_layers=16)
    if name in ("moonlight-16b-a3b", "deepseek-v2-236b"):
        # MLA without / with a query latent: a dense prefix and a unit
        cfg = dataclasses.replace(cfg, num_layers=4)
    return cfg


@pytest.mark.parametrize("name,attn_impl", [
    ("granite-3-2b", "xla"), ("deepseek-moe-16b", "xla"),
    ("jamba-1.5-large-398b", "xla"),
    ("granite-3-2b", "pallas_interpret"),   # the flash-decode kernel
    ("moonlight-16b-a3b", "xla"), ("deepseek-v2-236b", "xla"),   # MLA
])
def test_decode_rows_write_matches_mask_write(name, attn_impl):
    """Six decode steps from a prefilled cache, ragged per-slot positions
    (an idle slot at 0, one running from max_len - 2 off the end): the
    rows path gives the mask path's caches bit for bit and its greedy
    ids, and hidden states within bf16 rounding."""
    cfg = _write_path_cfg(name, attn_impl)
    rules = make_rules(cfg, None, None)
    mask_rules = _kv_seq_sharded_rules(rules)
    assert decode_cache_write(cfg, rules) == "rows"
    assert decode_cache_write(cfg, mask_rules) == "mask"
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p,
        init_params(model_specs(cfg), jax.random.PRNGKey(0)))
    B, max_len, P = 4, 24, 8
    rng = np.random.RandomState(0)
    cache = init_params(cache_specs(cfg, B, max_len), jax.random.PRNGKey(1),
                        dtype=None)
    pre = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, P))),
           "positions": jnp.broadcast_to(jnp.arange(P), (B, P))}
    _, cache, _, _ = forward(cfg, params, pre, rules=rules, cache=cache,
                             moe_impl="dense")

    def step(r):
        def f(c, b):
            x, c, _, _ = forward(cfg, params, b, rules=r, cache=c,
                                 moe_impl="dense")
            lg = logits_from_hidden(cfg, params, x, r, last_only=True)
            return x, jnp.argmax(lg[:, -1, :cfg.vocab_size], -1), c
        return jax.jit(f)

    f_rows, f_mask = step(rules), step(mask_rules)
    c_rows = c_mask = cache
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = np.array([P, 0, P - 3, max_len - 2], np.int32)
    advance = np.array([1, 0, 1, 1], np.int32)        # slot 1 idle
    for _ in range(6):
        b = {"tokens": tok, "positions": jnp.asarray(pos[:, None])}
        x_r, id_r, c_rows = f_rows(c_rows, b)
        x_m, id_m, c_mask = f_mask(c_mask, b)
        for a, m in zip(jax.tree.leaves(c_rows), jax.tree.leaves(c_mask)):
            assert a.shape == m.shape and a.dtype == m.dtype
            assert np.array_equal(np.asarray(a), np.asarray(m))
        np.testing.assert_array_equal(np.asarray(id_r), np.asarray(id_m))
        np.testing.assert_allclose(np.asarray(x_r, np.float32),
                                   np.asarray(x_m, np.float32),
                                   rtol=1e-2, atol=1e-2)
        tok = id_r[:, None].astype(jnp.int32)
        pos = pos + advance


@pytest.mark.parametrize("layers", [None, 3])     # prefix leaf, stacked
def test_write_rows_matches_the_mask_write(layers):
    """Each slot's row lands where the where-mask write puts it, across
    128-position windows of a 300-position cache (the last window clipped
    to the end), and a position off either end writes nothing."""
    from repro.models.attention import _update_cache, write_rows
    rng = np.random.RandomState(3)
    pos = jnp.asarray([0, 127, 128, 200, 255, 299, 300, -1], jnp.int32)
    B, S, KV, hd = len(pos), 300, 2, 4
    lead = () if layers is None else (layers,)
    cache = {n: jnp.asarray(rng.randn(*lead, B, S, KV, hd), jnp.bfloat16)
             for n in ("k", "v")}
    rows = {n: jnp.asarray(rng.randn(*lead, B, KV, hd), jnp.bfloat16)
            for n in ("k", "v")}
    got = jax.jit(write_rows)(cache, rows, pos)
    for n in ("k", "v"):
        want = jax.vmap(_update_cache, (0, 0, None))(
            cache[n].reshape(-1, B, S, KV, hd),
            rows[n].reshape(-1, B, 1, KV, hd), pos).reshape(cache[n].shape)
        assert np.array_equal(np.asarray(got[n]), np.asarray(want))
        changed = np.any(np.asarray(got[n] != cache[n]), axis=(-2, -1))
        assert changed.reshape(-1, B, S).any(0).sum(-1).tolist() == \
            [1, 1, 1, 1, 1, 1, 0, 0]


def _scan_out_shapes(cfg, rules, B=4, max_len=64):
    """Shapes of every output of the decode step's layer scans."""
    pspec = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
                         model_specs(cfg), is_leaf=is_spec)
    cspec = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                         cache_specs(cfg, B, max_len), is_leaf=is_spec)
    one = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    step = make_decode_sample_step(cfg, rules)
    jaxpr = jax.make_jaxpr(step)(pspec, {"tokens": one, "positions": one},
                                 cspec)
    return [v.aval.shape for e in jaxpr.eqns if e.primitive.name == "scan"
            for v in e.outvars]


def test_decode_scan_returns_rows_not_the_cache():
    """The reduced granite decode step's layer scan hands back the new
    (layers, B, KV, hd) rows, never a (layers, B, S, KV, hd) cache; the
    mask path (a sharded kv_seq) still does, which shows the check sees
    it."""
    cfg = get_config("granite-3-2b").reduced()
    rules = make_rules(cfg, None, None)
    n = cfg.layer_groups().repeats
    B, max_len = 4, 64
    stacked = (n, B, max_len, cfg.num_kv_heads, cfg.head_dim)
    rows = _scan_out_shapes(cfg, rules, B, max_len)
    assert stacked not in rows
    assert rows.count((n, B, cfg.num_kv_heads, cfg.head_dim)) == 2
    masked = _scan_out_shapes(cfg, _kv_seq_sharded_rules(rules), B, max_len)
    assert masked.count(stacked) == 2


@pytest.mark.parametrize("kv_seq,size,want", [
    (None, 2, "rows"),          # a mesh, kv_seq on no axis
    ("model", 1, "rows"),       # on an axis of size 1
    (("pod", "model"), 1, "rows"),
    ("model", 2, "mask"),       # sharded: llama-90b decode_32k's layout
    (("pod", "model"), 4, "mask"),
])
def test_decode_cache_write_follows_kv_seq_sharding(kv_seq, size, want):
    cfg = get_config("granite-3-2b").reduced()
    base = make_rules(cfg, None, None)
    assert base.mesh is None and decode_cache_write(cfg, base) == "rows"
    rules = ShardingRules(mesh=AbstractMesh((size,), ("model",)),
                          map=dict(base.map, kv_seq=kv_seq), enabled=False)
    assert decode_cache_write(cfg, rules) == want
    # a model without self-attention has no such write
    assert decode_cache_write(get_config("rwkv6-1.6b").reduced(),
                              rules) is None


def test_engine_reports_the_decode_write_path():
    from repro.serving import ServingEngine
    cfg = get_config("granite-3-2b").reduced()
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    rules = make_rules(cfg, None, None)
    eng = ServingEngine(cfg, params, rules, batch_slots=2, max_len=32)
    assert eng.stats()["decode_cache_write"] == "rows"
    eng = ServingEngine(cfg, params, _kv_seq_sharded_rules(rules),
                        batch_slots=2, max_len=32)
    assert eng.stats()["decode_cache_write"] == "mask"


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_pspec_dedup_drops_repeated_axis():
    rules = make_rules(None, None, None)
    rules.map = {"a": "model", "b": "model"}
    spec = rules.pspec(("a", "b"))
    assert spec[0] == "model" and spec[1] is None


def test_auto_batch_axes_divisibility():
    from repro.sharding.rules import _auto_batch_axes

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    assert _auto_batch_axes(FakeMesh(), ("pod", "data"), 256) == \
        ("pod", "data")
    assert _auto_batch_axes(FakeMesh(), ("pod", "data"), 1) is None
    assert _auto_batch_axes(FakeMesh(), ("pod", "data", "model"), 256) == \
        ("pod", "data")
    assert _auto_batch_axes(FakeMesh(), ("pod", "data"), 32) == \
        ("pod", "data")


def test_minitron_overrides_applied():
    cfg = get_config("minitron-4b")
    rules = make_rules(cfg, SHAPES["train_4k"], None)
    assert rules.map["heads"] is None
    assert rules.map["kv_heads"] is None
