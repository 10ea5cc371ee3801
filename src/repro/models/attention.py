"""GQA / MQA / cross attention with KV cache, qk-norm, RoPE.

Three attention-core implementations selected by cfg.attn_impl:
  * "xla"              — query-chunked attention in pure jnp (dry-run path)
  * "pallas"           — Pallas flash kernel (TPU target)
  * "pallas_interpret" — the same kernel, interpret=True (CPU validation)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm_nl
from repro.models.params import ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def attn_specs(cfg, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), (None,), init="ones")
        s["k_norm"] = ParamSpec((hd,), (None,), init="ones")
    if cross:
        # tanh-gated residual (llama-3.2-vision style)
        s["gate"] = ParamSpec((), (), init="zeros")
    return s


# ---------------------------------------------------------------------------
# Attention core (query-chunked, grouped)
# ---------------------------------------------------------------------------

def _attend_dense(q, k, v, q_pos, kv_valid_len, causal, scale):
    """q: (B,Sq,H,hd); k,v: (B,Skv,H,hd) (kv pre-expanded to H so the head
    dim shards cleanly over "model"). Full-Skv scores."""
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    skv = k.shape[1]
    kv_idx = jnp.arange(skv)
    mask = jnp.ones((q.shape[0], q.shape[1], skv), dtype=bool)
    if causal:
        mask &= kv_idx[None, None, :] <= q_pos[:, :, None]
    if kv_valid_len is not None:
        mask &= kv_idx[None, None, :] < kv_valid_len[:, None, None]
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    return out


def _attend_grouped(q, k, v, q_pos, kv_valid_len, causal, scale):
    """Non-expanding GQA attention for decode: q grouped (B,Sq,KV,G,hd)
    against the raw (B,Skv,KV,hd) cache — the expanded KV is never
    materialized (8x the cache at llama-90b decode_32k)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg,
                        k).astype(jnp.float32) * scale
    skv = k.shape[1]
    kv_idx = jnp.arange(skv)
    mask = jnp.ones((B, Sq, skv), dtype=bool)
    if causal:
        mask &= kv_idx[None, None, :] <= q_pos[:, :, None]
    if kv_valid_len is not None:
        mask &= kv_idx[None, None, :] < kv_valid_len[:, None, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def _attend_new_row(q, k, v, k_new, v_new, pos, scale):
    """Decode attention of q (B,1,H,hd) over the cache positions < pos of
    the layer's unmodified (B,S,KV,hd) slice plus the new (B,1,KV,hd) row
    at pos: :func:`_attend_grouped` over the slice with that row written,
    without building the written slice. The softmax's max and sum run
    over both parts in float32; a pos off the end of the slice (>= S)
    leaves the row out, as the where-mask write leaves the slice as it
    was."""
    B, _, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    qg = q.reshape(B, 1, KV, H // KV, hd)
    s_old = jnp.einsum("bqkgh,bskh->bkgqs", qg,
                       k).astype(jnp.float32) * scale
    s_new = jnp.einsum("bqkgh,bskh->bkgqs", qg,
                       k_new).astype(jnp.float32) * scale
    old_ok = jnp.arange(S)[None, :] < pos[:, None]
    s_old = jnp.where(old_ok[:, None, None, None, :], s_old, NEG_INF)
    s_new = jnp.where((pos < S)[:, None, None, None, None], s_new, NEG_INF)
    m = jnp.maximum(jnp.max(s_old, axis=-1, keepdims=True), s_new)
    e_old = jnp.exp(s_old - m)
    e_new = jnp.exp(s_new - m)
    den = jnp.sum(e_old, axis=-1, keepdims=True) + e_new
    w_old = (e_old / den).astype(v.dtype)
    w_new = (e_new / den).astype(v.dtype)
    out = (jnp.einsum("bkgqs,bskh->bqkgh", w_old, v,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgqs,bskh->bqkgh", w_new, v_new,
                        preferred_element_type=jnp.float32))
    return out.astype(v.dtype).reshape(B, 1, H, v.shape[-1])


def _expand_kv(k, H):
    """(B,S,KV,hd) -> (B,S,H,hd), repeating each kv head H/KV times.
    Keeps the head axis aligned with q heads so a 'heads->model' shard
    constraint partitions both identically (GQA groups never straddle
    a model shard because KV divides H)."""
    B, S, KV, hd = k.shape
    G = H // KV
    if G == 1:
        return k
    return jnp.repeat(k, G, axis=2)


def attention_core_xla(q, k, v, *, q_positions, kv_valid_len=None,
                       causal=True, chunk_q: int = 512, unroll=False):
    """q (B,Sq,H,hd), k/v (B,Skv,KVH,hd), q_positions (B,Sq) absolute.

    Chunked over Sq via lax.scan so the (Sq, Skv) score matrix is never
    fully materialized (XLA-level flash; the Pallas kernel also tiles Skv).
    """
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]          # may differ from hd (MLA)
    scale = 1.0 / (hd ** 0.5)
    if Sq <= 8:                 # decode: never expand the KV cache
        return _attend_grouped(q, k, v, q_positions, kv_valid_len, causal,
                               scale)
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)

    if Sq <= max(chunk_q, 16) or Sq % chunk_q != 0:
        return _attend_dense(q, k, v, q_positions, kv_valid_len, causal,
                             scale)

    n = Sq // chunk_q
    qs = q.reshape(B, n, chunk_q, H, hd).transpose(1, 0, 2, 3, 4)
    ps = q_positions.reshape(B, n, chunk_q).transpose(1, 0, 2)

    def body(_, qp):
        qc, pc = qp
        oc = _attend_dense(qc, k, v, pc, kv_valid_len, causal, scale)
        return None, oc

    _, outs = jax.lax.scan(body, None, (qs, ps), unroll=True if unroll else 1)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd_v)


def attention_core(cfg, q, k, v, **kw):
    impl = cfg.attn_impl
    if impl == "xla":
        return attention_core_xla(q, k, v, unroll=cfg.unroll_inner, **kw)
    from repro.kernels.flash_attention import ops as fa_ops
    interpret = impl == "pallas_interpret"
    if q.shape[1] == 1:
        from repro.kernels.decode_attention import ops as da_ops
        return da_ops.decode_attention(
            q, k, v, q_positions=kw["q_positions"],
            kv_valid_len=kw.get("kv_valid_len"), interpret=interpret)
    return fa_ops.flash_attention(
        q, k, v, q_positions=kw["q_positions"],
        kv_valid_len=kw.get("kv_valid_len"),
        causal=kw.get("causal", True), interpret=interpret)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def attn_cache_specs(cfg, batch: int, max_len: int, cross: bool = False,
                     n_vis: int = 0):
    """Returns {name: (shape, logical_axes)} for this layer's cache."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    if cross:
        return {
            "ck": ((batch, n_vis, KV, hd),
                   ("batch", "vis_tokens", "kv_heads", "head_dim")),
            "cv": ((batch, n_vis, KV, hd),
                   ("batch", "vis_tokens", "kv_heads", "head_dim")),
        }
    return {
        "k": ((batch, max_len, KV, hd),
              ("batch", "kv_seq", "kv_heads", "head_dim")),
        "v": ((batch, max_len, KV, hd),
              ("batch", "kv_seq", "kv_heads", "head_dim")),
    }


def _update_cache(cache_k, k_new, pos):
    """Per-sequence cache update at positions pos (B,).

    Three partition-friendly paths:
      * full overwrite (prefill writes the whole range): no read at all;
      * S==1 (decode) on a cache whose sequence dim is sharded: elementwise
        where-mask — works with ANY sharding of the sequence dim (a
        dynamic_update_slice at a traced index forces SPMD to all-gather a
        sharded cache: +19 GB/device at llama-90b decode_32k), at the cost
        of rewriting the whole slice every step;
      * partial prefill (serving engine): per-row dynamic update.

    The fourth write, for S==1 on a cache whose sequence dim is on no mesh
    axis (:func:`decode_cache_write` says "rows"), is not here: the layer
    returns only its new rows and ``model.forward`` writes them into the
    donated cache after the layer scan (:func:`write_rows`).
    """
    B, S_new = k_new.shape[:2]
    S = cache_k.shape[1]
    if S_new == S:
        return k_new.astype(cache_k.dtype)
    if S_new == 1:
        idx = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1)
        mask = (idx == pos[:, None])[:, :, None, None]
        return jnp.where(mask, k_new.astype(cache_k.dtype), cache_k)

    def upd(c, kn, p):
        return jax.lax.dynamic_update_slice(c, kn, (p, 0, 0))
    return jax.vmap(upd)(cache_k, k_new, pos)


def decode_cache_write(rules) -> str:
    """How a one-token decode step writes a self-attention layer's new K/V
    rows into a cache laid out by ``rules``: "rows" (the layer returns the
    rows and :func:`write_rows` writes them in place) when the cache's
    ``kv_seq`` axis is on no mesh axis of size > 1; "mask" (the where-mask
    of :func:`_update_cache`) when it is sharded."""
    if rules.mesh is None:
        return "rows"
    m = rules.map.get("kv_seq")
    axes = () if m is None else ((m,) if isinstance(m, str) else tuple(m))
    size = 1
    for a in axes:
        size *= rules.mesh.shape.get(a, 1) if a is not None else 1
    return "rows" if size == 1 else "mask"


def write_rows(cache, rows, pos, lead=None):
    """Write each slot's new row into the cache at its position: cache
    leaves (*L, B, S, *row), row leaves (*L, B, *row), pos (B,), with
    ``lead`` = len(L) leading (layer) axes; by default those of a K/V
    leaf, whose row is (KV, hd). Every slot is written; a position
    outside [0, S) writes nothing, as the where-mask write does.

    Per slot, the row goes into the 128-position window that holds it
    (read, where-mask, dynamic_update_slice back): on a donated cache XLA
    updates that window in place, in the layout the cache has. The TPU
    keeps this cache with its sequence axis minor-most, and a one-row
    update or a scatter makes its compiler copy the whole cache into
    another layout and back."""
    def put(c, r):
        r = r.astype(c.dtype)
        n = c.ndim - 4 if lead is None else lead
        S = c.shape[n + 1]
        tail = c.ndim - n - 2
        W = min(128, S)
        for b in range(pos.shape[0]):
            p = pos[b]
            s0 = jnp.clip((p // W) * W, 0, S - W)
            start = (0,) * n + (b, s0) + (0,) * tail
            win = jax.lax.dynamic_slice(
                c, start, c.shape[:n] + (1, W) + c.shape[n + 2:])
            hit = (jnp.arange(W) == p - s0)[(slice(None),) + (None,) * tail]
            win = jnp.where(hit, r[(Ellipsis, slice(b, b + 1), None)
                                   + (slice(None),) * tail], win)
            c = jax.lax.dynamic_update_slice(c, win, start)
        return c

    return {name: put(cache[name], rows[name]) for name in cache}


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------

def attention(cfg, params, x, *, rules, positions, cache=None,
              vision=None, cross: bool = False, rows_only: bool = False):
    """Pre-norm'd x -> attention output (+ updated cache).

    x: (B, S, D); positions: (B, S) absolute positions.
    cache: dict from attn_cache_specs (decode/prefill) or None (train).
    vision: (B, T_vis, D) projected patch embeddings (cross layers only).
    rows_only: a one-token decode whose cache ``kv_seq`` is unsharded
    (:func:`decode_cache_write`): the cache is only read, and the second
    result is the new rows {"k", "v"} (B, KV, hd) for :func:`write_rows`.
    """
    dt = x.dtype
    B, S, D = x.shape
    x = rules.constrain(x, ("batch", None, None))

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    if cfg.qk_norm:
        q = rmsnorm_nl(q, cfg.norm_eps) * params["q_norm"].astype(dt)

    if cross:
        assert vision is not None
        if cache is not None and "ck" in cache and S == 1:
            k, v = cache["ck"], cache["cv"]
            new_cache = cache
        else:
            k = jnp.einsum("btd,dhk->bthk", vision, params["wk"].astype(dt))
            v = jnp.einsum("btd,dhk->bthk", vision, params["wv"].astype(dt))
            if cfg.qk_norm:
                k = rmsnorm_nl(k, cfg.norm_eps) * params["k_norm"].astype(dt)
            new_cache = dict(cache, ck=k, cv=v) if cache is not None else None
        q = rules.constrain(q, ("batch", None, "heads", None))
        k = rules.constrain(k, ("batch", None, "kv_heads", None))
        v = rules.constrain(v, ("batch", None, "kv_heads", None))
        out = attention_core(cfg, q, k, v, q_positions=positions,
                             causal=False)
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
        out = out * jnp.tanh(params["gate"].astype(jnp.float32)).astype(dt)
        return out, new_cache

    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        k = rmsnorm_nl(k, cfg.norm_eps) * params["k_norm"].astype(dt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    q = rules.constrain(q, ("batch", None, "heads", None))
    k = rules.constrain(k, ("batch", None, "kv_heads", None))
    v = rules.constrain(v, ("batch", None, "kv_heads", None))

    if rows_only:
        pos0 = positions[:, 0]
        rows = {"k": k[:, 0].astype(cache["k"].dtype),
                "v": v[:, 0].astype(cache["v"].dtype)}
        k_new = rows["k"][:, None].astype(dt)
        v_new = rows["v"][:, None].astype(dt)
        if cfg.attn_impl == "xla":
            out = _attend_new_row(q, cache["k"].astype(dt),
                                  cache["v"].astype(dt), k_new, v_new,
                                  pos0, 1.0 / (q.shape[-1] ** 0.5))
        else:   # the Pallas decode kernel reads one written slice
            out = attention_core(
                cfg, q, _update_cache(cache["k"].astype(dt), k_new, pos0),
                _update_cache(cache["v"].astype(dt), v_new, pos0),
                q_positions=positions, kv_valid_len=pos0 + 1, causal=True)
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
        return out, rows

    new_cache = None
    kv_valid_len = None
    if cache is not None:
        pos0 = positions[:, 0]
        ck = _update_cache(cache["k"], k.astype(cache["k"].dtype), pos0)
        cv = _update_cache(cache["v"], v.astype(cache["v"].dtype), pos0)
        ck = rules.constrain(ck, ("batch", "kv_seq", "kv_heads", None))
        cv = rules.constrain(cv, ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": ck, "v": cv}
        k, v = ck.astype(dt), cv.astype(dt)
        kv_valid_len = positions[:, -1] + 1

    out = attention_core(cfg, q, k, v, q_positions=positions,
                         kv_valid_len=kv_valid_len, causal=True)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return out, new_cache
