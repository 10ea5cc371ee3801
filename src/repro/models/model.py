"""Unified block-pattern LM covering all 10 assigned architectures.

The model body is ``prefix blocks + (repeating unit) * k`` (configs.base
group_layers). Unit params/caches are stacked on a leading "layers" axis and
executed with lax.scan (small HLO, fast 512-device compiles). Per-block:

    x += mixer(norm(x))     mixer in {attn, cross, mla, mamba, rwkv-timemix}
    x += ffn(norm(x))       ffn   in {dense swiglu, moe, rwkv-channelmix}

Caches mirror the param structure; all leaves are ParamSpec so the same
machinery yields materialized buffers (smoke), ShapeDtypeStructs (dry-run)
and PartitionSpecs (pjit).
"""
from __future__ import annotations

from jax.ad_checkpoint import checkpoint_name as _ckpt_name

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.params import ParamSpec, stack_specs


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _block_specs(cfg, spec, ff_width: int) -> dict:
    mixer, ffn_kind = spec
    d = cfg.d_model
    s = {"norm1": L.rmsnorm_specs(d), "norm2": L.rmsnorm_specs(d)}
    if mixer == "attn":
        s["mixer"] = attn_mod.attn_specs(cfg)
    elif mixer == "cross":
        s["mixer"] = attn_mod.attn_specs(cfg, cross=True)
    elif mixer == "mla":
        s["mixer"] = mla_mod.mla_specs(cfg)
    elif mixer == "mamba":
        s["mixer"] = mamba_mod.mamba_specs(cfg)
    elif mixer == "rwkv":
        s["mixer"] = rwkv_mod.timemix_specs(cfg)
    else:
        raise ValueError(mixer)
    if ffn_kind == "dense":
        s["ffn"] = L.ffn_specs(d, ff_width)
    elif ffn_kind == "moe":
        s["ffn"] = moe_mod.moe_specs(cfg)
    elif ffn_kind == "rwkv":
        s["ffn"] = rwkv_mod.channelmix_specs(cfg)
    else:
        raise ValueError(ffn_kind)
    return s


def model_specs(cfg) -> dict:
    groups = cfg.layer_groups()
    specs = {"embed": L.embed_specs(cfg.padded_vocab, cfg.d_model,
                                    cfg.tie_embeddings),
             "final_norm": L.rmsnorm_specs(cfg.d_model)}
    if cfg.vision is not None:
        specs["frontend"] = L.frontend_specs(cfg.vision.raw_dim, cfg.d_model)
    specs["prefix"] = [
        _block_specs(cfg, sp, cfg.dense_ff_for(i))
        for i, sp in enumerate(groups.prefix)]
    specs["unit"] = [
        stack_specs(_block_specs(cfg, sp, cfg.d_ff), groups.repeats)
        for sp in groups.unit]
    return specs


def _block_cache_specs(cfg, spec, batch: int, max_len: int,
                       cache_dtype) -> dict:
    mixer, _ = spec
    if mixer in ("attn",):
        raw = attn_mod.attn_cache_specs(cfg, batch, max_len)
    elif mixer == "cross":
        raw = attn_mod.attn_cache_specs(
            cfg, batch, max_len, cross=True,
            n_vis=cfg.vision.num_tokens if cfg.vision else 0)
    elif mixer == "mla":
        raw = mla_mod.mla_cache_specs(cfg, batch, max_len)
    elif mixer == "mamba":
        raw = mamba_mod.mamba_cache_specs(cfg, batch)
    elif mixer == "rwkv":
        raw = rwkv_mod.rwkv_cache_specs(cfg, batch)
    else:
        raise ValueError(mixer)
    out = {}
    for k, (shape, axes) in raw.items():
        dt = jnp.float32 if k in ("ssm", "wkv") else cache_dtype
        out[k] = ParamSpec(tuple(shape), tuple(axes), init="zeros", dtype=dt)
    return out


def cache_specs(cfg, batch: int, max_len: int, cache_dtype=jnp.bfloat16):
    groups = cfg.layer_groups()
    return {
        "prefix": [_block_cache_specs(cfg, sp, batch, max_len, cache_dtype)
                   for sp in groups.prefix],
        "unit": [stack_specs(
            _block_cache_specs(cfg, sp, batch, max_len, cache_dtype),
            groups.repeats) for sp in groups.unit],
    }


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _apply_block(cfg, spec, params, x, *, rules, positions, cache,
                 vision, moe_impl, rows_only=False):
    mixer, ffn_kind = spec
    dt = x.dtype
    aux = jnp.zeros((), jnp.float32)
    sel = None

    h = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    new_cache = cache
    if mixer == "attn":
        out, nc = attn_mod.attention(cfg, params["mixer"], h, rules=rules,
                                     positions=positions, cache=cache,
                                     rows_only=rows_only)
    elif mixer == "cross":
        out, nc = attn_mod.attention(cfg, params["mixer"], h, rules=rules,
                                     positions=positions, cache=cache,
                                     vision=vision, cross=True)
    elif mixer == "mla":
        out, nc = mla_mod.mla_attention(cfg, params["mixer"], h, rules=rules,
                                        positions=positions, cache=cache,
                                        rows_only=rows_only)
    elif mixer == "mamba":
        out, nc = mamba_mod.mamba(cfg, params["mixer"], h, rules=rules,
                                  cache=cache,
                                  impl="xla" if cfg.attn_impl == "xla"
                                  else cfg.attn_impl)
    elif mixer == "rwkv":
        out, nc = rwkv_mod.time_mix(cfg, params["mixer"], h, rules=rules,
                                    cache=cache,
                                    impl="xla" if cfg.attn_impl == "xla"
                                    else cfg.attn_impl)
    out = _ckpt_name(out, "block_out")
    x = rules.constrain(x + out, ("batch", "seq_act", None))
    new_cache = nc

    h2 = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
    if ffn_kind == "dense":
        out2 = L.ffn(params["ffn"], h2, rules)
    elif ffn_kind == "moe":
        out2, aux, sel = moe_mod.moe(cfg, params["ffn"], h2, rules,
                                     impl=moe_impl)
    elif ffn_kind == "rwkv":
        out2, nc2 = rwkv_mod.channel_mix(cfg, params["ffn"], h2,
                                         rules=rules, cache=new_cache)
        if nc2 is not None:
            new_cache = nc2
    out2 = _ckpt_name(out2, "block_out")
    x = rules.constrain(x + out2, ("batch", "seq_act", None))
    return x, new_cache, aux, sel


def _maybe_remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    if cfg.remat == "comm":
        # communication-aware selective remat: save each block's post-
        # collective output so backward never re-runs forward's TP
        # all-reduces (Megatron-style selective recompute; costs 2x(B,S,D)
        # seq-sharded activations per layer).
        pol = jax.checkpoint_policies.save_only_these_names("block_out")
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def cast_big_params(cfg, params, rules):
    """Cast large (>=2-D, >64k elems) weights to compute dtype BEFORE the
    FSDP all-gather, pinning the cast with a sharding constraint. Halves
    gather bytes (f32 storage -> bf16 wire) and the associated temps; small
    / sensitive leaves (norm scales, biases, decay tables) stay f32."""
    cdt = jnp.dtype(cfg.compute_dtype)
    specs = model_specs(cfg)

    def cast(p, s):
        if (hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating)
                and p.dtype != cdt and p.ndim >= 2 and p.size > 65536):
            return rules.constrain(p.astype(cdt), s.axes)
        return p

    return jax.tree.map(cast, params, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


ROW_MIXERS = ("attn", "mla")     # blocks whose decode can return its rows


def decode_cache_write(cfg, rules):
    """The write path of a one-token decode step's self-attention K/V or
    latent rows (``attention.decode_cache_write``): "rows" or "mask";
    None for a model without self-attention or MLA blocks."""
    if not any(m in ROW_MIXERS for m, _ in cfg.layer_specs()):
        return None
    return attn_mod.decode_cache_write(rules)


def forward(cfg, params, batch, *, rules, cache=None, moe_impl="gshard",
            unroll=False):
    """Forward pass.

    batch: dict with
      "tokens"  (B,S) int32              (LM / vlm text)
      "frames"  (B,S,raw_dim)            (audio family: replaces tokens)
      "vision"  (B,Tv,raw_dim)           (vlm patch embeddings)
      "positions" (B,S) int32 absolute positions
    cache: cache tree (decode/prefill) or None (train)
    Returns (hidden (B,S,D), new_cache, aux_loss, routes): ``routes`` is
    the MoE layers' routing in layer order, {"sel": (MoE layers, B, S,
    top_k) int32 experts each token chose}, and {} for a model without
    MoE layers.

    The self-attention K/V cache and the MLA latent cache are written one
    of four ways (``attention._update_cache``, ``mla._update_cache_2d``):
    prefill overwrites them whole or per row; a one-token decode on a
    cache whose ``kv_seq`` is sharded where-masks the whole slice, layer
    by layer, and the scan returns the rewritten cache. A one-token
    decode on an unsharded ``kv_seq`` (:func:`decode_cache_write` is
    "rows") keeps the cache read-only in the layers: each returns its new
    rows ((B, KV, hd) K/V, (B, kv_lora) / (B, rope) latent), the scan
    stacks them, and ``attention.write_rows`` after the scan writes them
    into the cache passed in, in place when the caller donates it. Other
    cache leaves (cross-attention, mamba, rwkv) come back whole.
    """
    groups = cfg.layer_groups()
    cdt = jnp.dtype(cfg.compute_dtype)
    positions = batch["positions"]
    rows_only = (cache is not None and positions.shape[1] == 1
                 and decode_cache_write(cfg, rules) == "rows")

    def merge(spec, old, new, lead):
        """A block's cache output as the new cache: write rows_only
        attention or latent rows into the old cache, take any other as
        it is."""
        if rows_only and spec[0] == "attn":
            return attn_mod.write_rows(old, new, positions[:, 0])
        if rows_only and spec[0] == "mla":
            with jax.named_scope("mla.write"):
                return attn_mod.write_rows(old, new, positions[:, 0], lead)
        return new

    if "frames" in batch and cfg.family == "audio":
        x = L.frontend(params["frontend"], batch["frames"], cdt) \
            if "frontend" in params else batch["frames"].astype(cdt)
        if "tokens" in batch:   # decode continues from generated tokens
            x = x + L.embed(params["embed"], batch["tokens"], cdt)
    else:
        x = L.embed(params["embed"], batch["tokens"], cdt)
    x = rules.constrain(x, ("batch", "seq_act", None))

    vision = None
    if cfg.vision is not None and "vision" in batch:
        vision = L.frontend(params["frontend"], batch["vision"], cdt)
        vision = rules.constrain(vision, ("batch", None, None))

    aux_total = jnp.zeros((), jnp.float32)
    sels = []                   # per MoE block, leading layer axis
    new_prefix_caches = []
    for i, sp in enumerate(groups.prefix):
        c = cache["prefix"][i] if cache is not None else None

        def pre_block(x_, params_, cache_):
            return _apply_block(cfg, sp, params_, x_, rules=rules,
                                positions=positions, cache=cache_,
                                vision=vision, moe_impl=moe_impl,
                                rows_only=rows_only)
        x, nc, aux, sel = _maybe_remat(cfg, pre_block)(
            x, params["prefix"][i], c)
        new_prefix_caches.append(merge(sp, c, nc, 0))
        aux_total += aux
        if sel is not None:
            sels.append(sel[None])

    new_unit_caches = [None] * len(groups.unit)
    if groups.repeats:
        unit_params = tuple(params["unit"])
        unit_caches = (tuple(cache["unit"]) if cache is not None
                       else tuple([None] * len(groups.unit)))

        def unit_body(carry, xs):
            x_, aux_ = carry
            p_slices, c_slices = xs
            ncs, unit_sel = [], []
            for pos_i, sp in enumerate(groups.unit):
                x_, nc, aux_i, sel = _apply_block(
                    cfg, sp, p_slices[pos_i], x_, rules=rules,
                    positions=positions, cache=c_slices[pos_i],
                    vision=vision, moe_impl=moe_impl, rows_only=rows_only)
                ncs.append(nc)
                aux_ = aux_ + aux_i
                if sel is not None:
                    unit_sel.append(sel)
            return (x_, aux_), (tuple(ncs), tuple(unit_sel))

        body = _maybe_remat(cfg, unit_body) if cfg.remat != "none" else unit_body
        (x, aux_total), (new_stacked, unit_sel) = jax.lax.scan(
            body, (x, aux_total), (unit_params, unit_caches),
            unroll=True if unroll else 1)
        new_unit_caches = [merge(sp, old, new, 1) for sp, old, new in
                           zip(groups.unit, unit_caches, new_stacked)]
        if unit_sel:            # (repeats, blocks, ...) in layer order
            sels.append(jnp.stack(unit_sel, 1).reshape(
                -1, *unit_sel[0].shape[1:]))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    new_cache = None
    if cache is not None:
        new_cache = {"prefix": new_prefix_caches, "unit": new_unit_caches}
    routes = {"sel": jnp.concatenate(sels)} if sels else {}
    return x, new_cache, aux_total, routes


def logits_from_hidden(cfg, params, x, rules, last_only: bool = False):
    if last_only:
        x = x[:, -1:, :]
    x = rules.constrain(x, ("batch", None, None))
    logits = L.unembed(params["embed"] if cfg.tie_embeddings
                       else {**params["embed"]}, x, cfg.tie_embeddings)
    return rules.constrain(logits, ("batch", None, "vocab"))


def lm_loss_fused(cfg, params, x, targets, rules, chunk: int = 512):
    unroll = cfg.unroll_inner
    """Fused unembed + cross-entropy, chunked over the sequence so the
    (B,S,padded_vocab) logits tensor is never materialized (the unfused
    version costs ~13 GB/device at train_4k scale)."""
    B, S, D = x.shape
    x = rules.constrain(x, ("batch", None, None))
    vp = cfg.padded_vocab
    w = (params["embed"]["tok"].T if cfg.tie_embeddings
         else params["embed"]["unembed"])
    chunk = min(chunk, S)
    if S % chunk != 0:
        chunk = S
    n = S // chunk
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)

    def body(acc, xt):
        xc, tc = xt
        lg = jnp.einsum("bsd,dv->bsv", xc, w.astype(xc.dtype))
        lg = rules.constrain(lg, ("batch", None, "vocab"))
        lf = lg.astype(jnp.float32)
        if vp != cfg.vocab_size:
            lf = jnp.where(jnp.arange(vp) < cfg.vocab_size, lf, -1e30)
        lse = jax.nn.logsumexp(lf, axis=-1)
        oh = jax.nn.one_hot(tc, vp, dtype=jnp.float32)
        tgt = jnp.sum(lf * oh, axis=-1)
        return acc + jnp.sum(lse - tgt), None

    body = jax.checkpoint(body)
    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts),
                          unroll=True if unroll else 1)
    return tot / (B * S)


def lm_loss(cfg, logits, targets, rules):
    """Cross-entropy with vocab-sharded logits (one-hot contraction fuses).
    Logits are over the PADDED vocab; pad columns are masked out."""
    lf = logits.astype(jnp.float32)
    vp = cfg.padded_vocab
    if vp != cfg.vocab_size:
        pad_mask = jnp.arange(vp) < cfg.vocab_size
        lf = jnp.where(pad_mask, lf, -1e30)
    lse = jax.nn.logsumexp(lf, axis=-1)
    oh = jax.nn.one_hot(targets, vp, dtype=jnp.float32)
    tgt = jnp.sum(lf * oh, axis=-1)
    return jnp.mean(lse - tgt)
