"""Mixture-of-experts FFN: shared experts + routed top-k experts.

Routing (:func:`route`) is the config's rule: softmax or sigmoid scores
over every routed expert, a per-expert selection bias added for the
top-k choice only, the chosen scores renormalised and scaled. The layer
holds the experts of ``MoEConfig.held`` (all by default, or one chip's
share under expert parallelism): it routes over all of them and
computes the part of the result its own experts give; what the others
would add is left out.

Implementations of the routed part (impl arg, chosen by caller):
  * "dense"  — every held expert computes every token; exact oracle for
    tests.
  * "gshard" — group-wise capacity dispatch (GShard/MaxText "dropping"
    style). Tokens are split into groups of <=4096; each group dispatches
    into per-expert capacity slots via a (G, Tg, E, C) mask sharded
    experts->model, so the per-device transient stays ~tens of MB. Expert
    compute is local to the model shard; the combine einsum contracts the
    expert axis and all-reduces over "model" — the EP collective of the
    baseline. (The hillclimb alternative, core/ep_a2a.py, replaces this
    with a shard_map all-to-all.) Tokens past an expert's capacity are
    dropped.
  * "ragged" — dropless, the serving path's impl, in one of two forms
    chosen by the call's token count T = B * S (:func:`routed_form`):
    "grouped" sorts the (token, expert) assignments by held expert and
    runs one grouped matmul (``jax.lax.ragged_dot``) per projection, so
    each held expert computes only the tokens routed to it; "dense",
    for T <= ``DENSE_TOKENS`` (a decode step), computes every held
    expert for every token in three einsums over the held slabs and
    weighs each result by its routing weight (0 where the token did not
    choose that expert). A layer scan's slice of the expert stack fuses
    into an einsum but not into ``ragged_dot``, which takes a copy of
    each slab; at a few tokens every held expert is hit anyway, so the
    dense form reads the weights once and its extra FLOPs cost little.

The layer also returns its routing, the experts each token chose
(``sel``), from which the serving steps count the assignments each
held expert received. Its parts run under the
name scopes ``moe.route``, ``moe.experts`` (the expert projections of
"ragged", either form), ``moe.combine`` and ``moe.shared``, which reach
the device trace.

Aux (load-balance) loss is returned for training.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec

GROUP_TOKENS = 4096
# tokens a "ragged" call dispatches at once: bounds the sorted copy of
# the assignments, (chunk * top_k, d_model), in a long prefill
RAGGED_TOKENS = 4096
# largest T a "ragged" call computes in the dense form. Every held
# expert then multiplies every token: 2 * T FLOPs per bf16 weight
# element, T FLOPs per weight byte, so the form stays bound by reading
# the weights while T is well under v5e's ridge (197 TFLOP/s over
# 819 GB/s, ~240 FLOP/byte); 64 is a quarter of it and four times a
# 16-slot decode step
DENSE_TOKENS = 64


def moe_specs(cfg) -> dict:
    mo, d = cfg.moe, cfg.d_model
    n = mo.held_range[1]
    s = {
        "router": ParamSpec((d, mo.num_experts), ("embed", "experts"),
                            scale=0.02),
        "w_gate": ParamSpec((n, d, mo.expert_ff),
                            ("experts", "embed", "expert_mlp")),
        "w_up":   ParamSpec((n, d, mo.expert_ff),
                            ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((n, mo.expert_ff, d),
                            ("experts", "expert_mlp", "embed")),
    }
    if mo.score_bias:
        s["router_bias"] = ParamSpec((mo.num_experts,), (None,),
                                     init="zeros")
    if mo.num_shared:
        s["shared"] = {
            "w_gate": ParamSpec((d, mo.shared_ff), ("embed", "mlp")),
            "w_up":   ParamSpec((d, mo.shared_ff), ("embed", "mlp")),
            "w_down": ParamSpec((mo.shared_ff, d), ("mlp", "embed")),
        }
    return s


def route(mo, x, router, bias=None):
    """x (..., D) -> (weights (..., K) f32, sel (..., K) int32, aux).

    Logits in float32 over all ``num_experts``; scores by
    ``mo.scoring``; the top-k of scores + ``bias`` (the bias picks, it
    does not weigh); the chosen scores renormalised when
    ``mo.norm_topk``, then times ``mo.routed_scale``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    if mo.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / scores.sum(-1, keepdims=True)
    elif mo.scoring == "softmax":
        scores = probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown MoE scoring {mo.scoring!r}")
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, sel = jax.lax.top_k(choice, mo.top_k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if mo.norm_topk:
        weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    weights = weights * mo.routed_scale
    # Switch-style load-balance aux loss
    lead = tuple(range(probs.ndim - 1))
    me = probs.mean(axis=lead)                                # (E,)
    ce = jnp.mean(jax.nn.one_hot(sel, mo.num_experts, dtype=jnp.float32),
                  axis=lead + (probs.ndim - 1,))
    aux = mo.router_aux_coef * mo.num_experts * jnp.sum(me * ce) * mo.top_k
    return weights, sel.astype(jnp.int32), aux


class Routing(NamedTuple):
    """One layer's routing of T tokens, in the held experts' terms."""
    sel: jax.Array        # (T, K) the experts chosen, of all num_experts
    local: jax.Array      # (T, K) held-expert index, ``n`` where not held
    weights: jax.Array    # (T, K) f32, 0 where not held
    aux: jax.Array        # load-balance loss


def _route_tokens(cfg, params, xt) -> Routing:
    mo = cfg.moe
    first, n = mo.held_range
    w, sel, aux = route(mo, xt, params["router"], params.get("router_bias"))
    local = sel - first
    mine = (local >= 0) & (local < n)
    local = jnp.where(mine, local, n)
    return Routing(sel, local, jnp.where(mine, w, 0.0), aux)


def _expert_ffn(params, h, dt):
    """h: (G, E, C, D) per-expert token slabs -> (G, E, C, D)."""
    g = jnp.einsum("gecd,edf->gecf", h, params["w_gate"].astype(dt))
    u = jnp.einsum("gecd,edf->gecf", h, params["w_up"].astype(dt))
    a = jax.nn.silu(g) * u
    return jnp.einsum("gecf,efd->gecd", a, params["w_down"].astype(dt))


def _capacity(cfg, tg: int) -> int:
    mo = cfg.moe
    c = int(mo.top_k * tg / mo.num_experts * mo.capacity_factor)
    return max(-(-c // 4) * 4, 4)


def _gshard(cfg, params, x, rules, r: Routing):
    mo = cfg.moe
    n = mo.held_range[1]
    dt = x.dtype
    B, S, D = x.shape
    x = rules.constrain(x, ("batch", None, None))
    tg = min(S, GROUP_TOKENS)
    G = B * S // tg
    xg = x.reshape(G, tg, D)
    xg = rules.constrain(xg, ("batch", None, None))
    K = mo.top_k
    sel = r.local.reshape(G, tg, K)
    gates = r.weights.reshape(G, tg, K)
    C = _capacity(cfg, tg)

    # Position of each (token, k) in its expert's queue, counted per group.
    oh = jax.nn.one_hot(sel, n, dtype=jnp.float32)            # (G,Tg,K,n)
    oh = rules.constrain(oh, ("batch", None, None, "experts"))
    # flatten (Tg,K) in token-major order so earlier tokens win slots
    ohf = oh.reshape(G, tg * K, n)
    pos = jnp.cumsum(ohf, axis=1) * ohf - 1.0                 # (G,Tg*K,n)
    pos = pos.max(axis=-1).reshape(G, tg, K)                  # slot per (t,k)
    keep = (pos >= 0) & (pos < C)
    pos = jnp.clip(pos, 0, C - 1).astype(jnp.int32)

    gates_f = gates * keep                                     # drop overflow
    # dispatch/combine mask built per-k to bound the transient:
    # (G, Tg, n, C) accumulated over K, sharded experts->model.
    def add_k(carry, k_idx):
        m = (jax.nn.one_hot(sel[:, :, k_idx], n, dtype=jnp.float32)
             [..., None]
             * jax.nn.one_hot(pos[:, :, k_idx], C, dtype=jnp.float32)
             [:, :, None, :])
        m = m * gates_f[:, :, k_idx][..., None, None]
        return carry + rules.constrain(m, ("batch", None, "experts", None)), None

    combine = jnp.zeros((G, tg, n, C), dtype=jnp.float32)
    combine = rules.constrain(combine, ("batch", None, "experts", None))
    for k_idx in range(K):
        combine, _ = add_k(combine, k_idx)
    dispatch = (combine > 0).astype(dt)

    h = jnp.einsum("gtec,gtd->gecd", dispatch, xg)            # local dispatch
    h = rules.constrain(h, ("batch", "experts", None, None))
    y = _expert_ffn(params, h, dt)
    y = rules.constrain(y, ("batch", "experts", None, None))
    # combine: contracts experts (model-sharded) -> all-reduce over model
    out = jnp.einsum("gtec,gecd->gtd", combine.astype(dt), y)
    out = rules.constrain(out, ("batch", None, None))
    return out.reshape(B, S, D)


def _held_weights(local, weights, n):
    """(T, K) held indices and routing weights -> (T, n) float32: each
    token's weight on each held expert, 0 where it did not choose it.
    Choices of no held expert (local == n) one-hot to zeros."""
    return jnp.sum(jax.nn.one_hot(local, n, dtype=jnp.float32)
                   * weights[..., None], axis=1)


def _dense(cfg, params, x, rules, r: Routing):
    """Oracle: run all held experts on all tokens, weight by gates."""
    n = cfg.moe.held_range[1]
    dt = x.dtype
    B, S, D = x.shape
    xt = x.reshape(1, B * S, D)
    h = jnp.broadcast_to(xt[0][None], (n, B * S, D))[None]    # (1,n,T,D)
    y = _expert_ffn(params, h, dt)                            # (1,n,T,D)
    w = _held_weights(r.local, r.weights, n)[None]            # (1,T,n)
    return jnp.einsum("gte,getd->gtd", w.astype(dt), y).reshape(B, S, D)


def _grouped(params, xc, local, weights, n, dt):
    """One chunk of tokens through the held experts, dropless: xc (c, D),
    local / weights (c, K) -> (c, D). The c * K assignments are sorted by
    held expert (those of no held expert last) and each projection is one
    ``ragged_dot`` over the held experts' groups."""
    c, K = local.shape
    A = c * K
    e = local.reshape(A)
    order = jnp.argsort(e, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(e, n, dtype=jnp.int32), axis=0)
    rows = xc[order // K]                                     # (A, D)
    with jax.named_scope("moe.experts"):
        g = jax.lax.ragged_dot(rows, params["w_gate"].astype(dt), sizes)
        u = jax.lax.ragged_dot(rows, params["w_up"].astype(dt), sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u,
                               params["w_down"].astype(dt), sizes)
    with jax.named_scope("moe.combine"):
        # rows past the held groups belong to no expert: zero, not
        # whatever the grouped matmul left there
        y = jnp.where((jnp.arange(A) < sizes.sum())[:, None], y, 0)
        inv = jnp.zeros((A,), jnp.int32).at[order].set(
            jnp.arange(A, dtype=jnp.int32))
        y = y[inv].reshape(c, K, -1)
        return jnp.einsum("ck,ckd->cd", weights.astype(dt), y,
                          preferred_element_type=jnp.float32).astype(dt)


def _every_held(params, xt, local, weights, n, dt):
    """xt (T, D) through every held expert, dropless: local / weights
    (T, K) -> (T, D). Each expert's result is weighed by the routing
    weight of the token's choice of it, 0 where it was not chosen."""
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("td,edf->etf", xt, params["w_gate"].astype(dt))
        u = jnp.einsum("td,edf->etf", xt, params["w_up"].astype(dt))
        y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u,
                       params["w_down"].astype(dt))
    with jax.named_scope("moe.combine"):
        w = _held_weights(local, weights, n)                  # (T, n)
        return jnp.einsum("te,etd->td", w.astype(dt), y,
                          preferred_element_type=jnp.float32).astype(dt)


def routed_form(tokens: int) -> str:
    """The form a "ragged" call of ``tokens`` tokens takes: "dense"
    (every held expert on every token) up to ``DENSE_TOKENS``, else
    "grouped" (``ragged_dot`` over the assignments)."""
    return "dense" if tokens <= DENSE_TOKENS else "grouped"


def _ragged(cfg, params, x, rules, r: Routing):
    n = cfg.moe.held_range[1]
    dt = x.dtype
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    if routed_form(T) == "dense":
        return _every_held(params, xt, r.local, r.weights, n,
                           dt).reshape(B, S, D)
    chunk = RAGGED_TOKENS if T > RAGGED_TOKENS and T % RAGGED_TOKENS == 0 \
        else T
    if chunk == T:
        out = _grouped(params, xt, r.local, r.weights, n, dt)
    else:
        k = r.local.shape[-1]
        out = jax.lax.map(
            lambda a: _grouped(params, a[0], a[1], a[2], n, dt),
            (xt.reshape(-1, chunk, D), r.local.reshape(-1, chunk, k),
             r.weights.reshape(-1, chunk, k)))
    return out.reshape(B, S, D)


_IMPLS = {"dense": _dense, "gshard": _gshard, "ragged": _ragged}


def moe(cfg, params, x, rules, impl: str = "gshard"):
    """x (B,S,D) -> (out, aux, sel): the held experts' part of the
    routed result plus the shared experts, the load-balance loss, and
    the (B, S, K) int32 experts each token chose."""
    mo = cfg.moe
    dt = x.dtype
    B, S, D = x.shape
    with jax.named_scope("moe.route"):
        r = _route_tokens(cfg, params, x.reshape(B * S, D))
    sel = r.sel.reshape(B, S, mo.top_k)
    if impl == "a2a":
        from repro.core.ep_a2a import moe_a2a
        out, aux = moe_a2a(cfg, params, x, rules)
        return out, aux, sel
    out = _IMPLS[impl](cfg, params, x, rules, r)
    if mo.num_shared:
        with jax.named_scope("moe.shared"):
            out = out + _shared(params, x, dt, rules)
    return out, r.aux.astype(jnp.float32), sel


def moe_gshard(cfg, params, x, rules):
    """x: (B,S,D) -> (out, aux), the "gshard" impl."""
    return moe(cfg, params, x, rules, impl="gshard")[:2]


def moe_dense(cfg, params, x, rules):
    """x: (B,S,D) -> (out, aux), the "dense" oracle."""
    return moe(cfg, params, x, rules, impl="dense")[:2]


def _shared(params, x, dt, rules):
    p = params["shared"]
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    h = rules.constrain(jax.nn.silu(g) * u, ("batch", None, "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
