"""Multi-head latent attention (DeepSeek-V2 / V3).

Two execution paths:
  * expand   (train / prefill): decompress the latent into per-head K/V and
    run standard MHA.
  * absorbed (decode): fold W_k^b into the query and W_v^b into the output,
    attending directly over the compressed latent cache — the MLA memory
    saving (cache = kv_lora + rope_dim per token instead of 2*H*hd).

The query comes through a latent (``wq_a``, ``q_norm``, ``wq_b``) or,
with ``q_lora_rank`` 0, straight from the input (``wq``). The latent
cache is written as the GQA cache is (``model.forward``): a one-token
decode on an unsharded ``kv_seq`` only reads it and returns its new
``ckv`` / ``krope`` rows, which ``attention.write_rows`` writes after
the layer scan; otherwise the layer writes it (``_update_cache_2d``).
Name scopes ``mla.attend`` (projections and attention) and ``mla.write``
(the cache write) reach the device trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import attention_core
from repro.models.layers import apply_rope, rmsnorm_nl
from repro.models.params import ParamSpec

NEG_INF = -1e30


def mla_specs(cfg) -> dict:
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        s = {
            "wq_a":   ParamSpec((d, m.q_lora_rank), ("embed", "lora")),
            "q_norm": ParamSpec((m.q_lora_rank,), (None,), init="ones"),
            "wq_b":   ParamSpec((m.q_lora_rank, H, qk),
                                ("lora", "heads", "head_dim")),
        }
    else:
        s = {"wq": ParamSpec((d, H, qk), ("embed", "heads", "head_dim"))}
    return {
        **s,
        "wkv_a":  ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                            ("embed", "lora")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), (None,), init="ones"),
        "wk_b":   ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                            ("lora", "heads", "head_dim")),
        "wv_b":   ParamSpec((m.kv_lora_rank, H, m.v_head_dim),
                            ("lora", "heads", "head_dim")),
        "wo":     ParamSpec((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def mla_cache_specs(cfg, batch: int, max_len: int):
    m = cfg.mla
    return {
        "ckv":   ((batch, max_len, m.kv_lora_rank), ("batch", "kv_seq", "lora")),
        "krope": ((batch, max_len, m.qk_rope_head_dim),
                  ("batch", "kv_seq", None)),
    }


def _update_cache_2d(cache, new, pos):
    """Sharding-friendly (B, S, d) cache update (see attention._update_cache
    for rationale)."""
    B, S_new = new.shape[:2]
    S = cache.shape[1]
    if S_new == S:
        return new.astype(cache.dtype)
    if S_new == 1:
        idx = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1)
        mask = (idx == pos[:, None])[:, :, None]
        return jnp.where(mask, new.astype(cache.dtype), cache)

    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (p, 0))
    return jax.vmap(upd)(cache, new.astype(cache.dtype), pos)


def _rope(cfg, x, positions):
    """Rotary positions on the rope dims. In the interleaved layout the
    dims rotate in adjacent pairs: they are gathered into two halves
    first, as DeepSeek-V3's code does, and rotated as halves."""
    if cfg.mla.rope_interleave:
        r = x.shape[-1]
        x = x.reshape(*x.shape[:-1], r // 2, 2).swapaxes(-1, -2) \
            .reshape(x.shape)
    return apply_rope(x, positions, cfg.rope_theta)


def _latents(cfg, params, x, positions, dt):
    m = cfg.mla
    if m.q_lora_rank:
        cq = jnp.einsum("bsd,dl->bsl", x, params["wq_a"].astype(dt))
        cq = rmsnorm_nl(cq, cfg.norm_eps) * params["q_norm"].astype(dt)
        q = jnp.einsum("bsl,lhk->bshk", cq, params["wq_b"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = _rope(cfg, q[..., m.qk_nope_head_dim:], positions)

    kv = jnp.einsum("bsd,dl->bsl", x, params["wkv_a"].astype(dt))
    ckv = rmsnorm_nl(kv[..., :m.kv_lora_rank], cfg.norm_eps) \
        * params["kv_norm"].astype(dt)
    krope = _rope(cfg, kv[..., m.kv_lora_rank:], positions)
    return q_nope, q_rope, ckv, krope


def mla_attention(cfg, params, x, *, rules, positions, cache=None,
                  rows_only: bool = False):
    """x: (B,S,D). Returns (out, new_cache). ``rows_only``: a one-token
    decode whose cache ``kv_seq`` is unsharded
    (``model.decode_cache_write``): the cache is only read, and the
    second result is the new rows {"ckv" (B, kv_lora), "krope" (B, rope)}
    for ``attention.write_rows``."""
    dt = x.dtype
    m = cfg.mla
    B, S, D = x.shape
    x = rules.constrain(x, ("batch", None, None))
    with jax.named_scope("mla.attend"):
        q_nope, q_rope, ckv, krope = _latents(cfg, params, x, positions, dt)

    new_cache = None
    if rows_only:
        rows = {"ckv": ckv[:, 0].astype(cache["ckv"].dtype),
                "krope": krope[:, 0].astype(cache["krope"].dtype)}
        with jax.named_scope("mla.attend"):
            out = _absorbed_decode(cfg, params, q_nope, q_rope,
                                   cache["ckv"], cache["krope"],
                                   positions[:, 0], dt, new=rows)
        return out, rows
    if cache is not None:
        pos0 = positions[:, 0]
        with jax.named_scope("mla.write"):
            cckv = _update_cache_2d(cache["ckv"], ckv, pos0)
            ckro = _update_cache_2d(cache["krope"], krope, pos0)
            cckv = rules.constrain(cckv, ("batch", "kv_seq", None))
            ckro = rules.constrain(ckro, ("batch", "kv_seq", None))
        new_cache = {"ckv": cckv, "krope": ckro}
        if S == 1:
            with jax.named_scope("mla.attend"):
                out = _absorbed_decode(cfg, params, q_nope, q_rope, cckv,
                                       ckro, pos0, dt)
            return out, new_cache
        ckv, krope = cckv.astype(dt), ckro.astype(dt)

    # expand path --------------------------------------------------------
    with jax.named_scope("mla.attend"):
        k_nope = jnp.einsum("bsl,lhk->bshk", ckv, params["wk_b"].astype(dt))
        v = jnp.einsum("bsl,lhk->bshk", ckv, params["wv_b"].astype(dt))
        H = cfg.num_heads
        k_rope = jnp.broadcast_to(krope[:, :, None, :],
                                  (*krope.shape[:2], H, m.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        q = rules.constrain(q, ("batch", None, "heads", None))
        k = rules.constrain(k, ("batch", None, "heads", None))
        v = rules.constrain(v, ("batch", None, "heads", None))
        kv_valid_len = positions[:, -1] + 1 if cache is not None else None
        out = attention_core(cfg, q, k, v, q_positions=positions,
                             kv_valid_len=kv_valid_len, causal=True)
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return out, new_cache


def _absorbed_decode(cfg, params, q_nope, q_rope, ckv, krope, pos, dt,
                     new=None):
    """Decode without decompressing: score against the latent directly.

    ckv (B,S,kv_lora), krope (B,S,rope), pos (B,). Without ``new`` the
    slice already holds the step's row and the query attends to its
    positions <= pos. With ``new`` (the step's rows, {"ckv" (B,kv_lora),
    "krope" (B,rope)}) the slice is left as it was: the query attends to
    its positions < pos and to the new row at pos, one softmax over both
    in float32; a pos off the end (>= S) leaves the row out, as the
    where-mask write leaves the slice."""
    m = cfg.mla
    scale = 1.0 / ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5)
    # fold W_k^b into q:  (B,1,H,nope) x (lora,H,nope) -> (B,1,H,lora)
    q_abs = jnp.einsum("bqhn,lhn->bqhl", q_nope, params["wk_b"].astype(dt))
    s_l = jnp.einsum("bqhl,bsl->bhqs", q_abs, ckv.astype(dt))
    s_r = jnp.einsum("bqhr,bsr->bhqs", q_rope, krope.astype(dt))
    scores = (s_l + s_r).astype(jnp.float32) * scale
    kv_idx = jnp.arange(ckv.shape[1])
    if new is None:
        mask = kv_idx[None, :] <= pos[:, None]                # (B, Skv)
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        ctx = jnp.einsum("bhqs,bsl->bqhl", w, ckv.astype(dt))  # latent ctx
    else:
        n_ckv = new["ckv"].astype(dt)[:, None]                 # (B,1,lora)
        n_kr = new["krope"].astype(dt)[:, None]
        s_new = (jnp.einsum("bqhl,bsl->bhqs", q_abs, n_ckv)
                 + jnp.einsum("bqhr,bsr->bhqs", q_rope, n_kr)
                 ).astype(jnp.float32) * scale                 # (B,H,1,1)
        old_ok = kv_idx[None, :] < pos[:, None]
        S = ckv.shape[1]
        scores = jnp.where(old_ok[:, None, None, :], scores, NEG_INF)
        s_new = jnp.where((pos < S)[:, None, None, None], s_new, NEG_INF)
        mx = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), s_new)
        e_old = jnp.exp(scores - mx)
        e_new = jnp.exp(s_new - mx)
        den = jnp.sum(e_old, axis=-1, keepdims=True) + e_new
        # both parts summed in float32 and rounded once, as the one
        # einsum over the written slice rounds; q (length 1) is dropped
        # for the dot and put back
        ctx = (jnp.einsum("bhs,bsl->bhl", (e_old / den)[:, :, 0].astype(dt),
                          ckv.astype(dt), preferred_element_type=jnp.float32)
               + jnp.einsum("bhs,bsl->bhl", (e_new / den)[:, :, 0].astype(dt),
                            n_ckv, preferred_element_type=jnp.float32)
               ).astype(dt)[:, None]
    out = jnp.einsum("bqhl,lhk->bqhk", ctx, params["wv_b"].astype(dt))
    out = jnp.einsum("bqhk,hkd->bqd", out, params["wo"].astype(dt))
    return out
