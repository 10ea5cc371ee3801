"""Gather-based expert-parallel MoE (beyond-paper hillclimb optimization).

The GShard-style baseline (models/moe.moe_gshard) dispatches through
(G, Tg, E, C) one-hot mask einsums whose contraction FLOPs are
O(T * kT * D) — quadratic in tokens — and whose masks dominate transient
memory. This implementation runs under shard_map: every model-shard owns
E/model_size experts, selects its tokens with a LOCAL gather (no mask
einsum, no dispatch collective — tokens are already replicated across the
model axis by the sequence-parallel layout), runs its experts, and
scatter-adds partial outputs which one psum over "model" combines — the
same wire bytes as the baseline's combine all-reduce, with the quadratic
dispatch compute deleted.

Faithful ST framing: the per-expert gathers/scatters are the "merged
kernels" and the single psum is the aggregated put of the access epoch.
``build_moe_a2a_program`` makes that framing LITERAL: the combine is
lowered onto the triggered-op DAG as an aggregated-put access epoch —
each shard's partial output is a payload put to every peer shift and the
combine kernel sums the received partials — so the schedule passes and
all three backends apply to expert parallelism unchanged.
``moe_a2a_st`` runs it and matches :func:`moe_a2a` numerically.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core.compat import shard_map
from repro.core.patterns import register_pattern, shifts_topology
from repro.configs.base import MoEConfig
from repro.models.moe import _capacity, _shared, route


def moe_a2a(cfg, params, x, rules):
    """x: (B,S,D) -> (out, aux). Requires rules.mesh with a "model" axis."""
    mo = cfg.moe
    dt = x.dtype
    B, S, D = x.shape
    mesh = rules.mesh
    if mesh is None or "model" not in mesh.axis_names:
        # single-device fallback: one shard owning all experts
        return _moe_local(cfg, params, x, rules, n_shards=1, shard_id=0)

    x = rules.constrain(x, ("batch", None, None))
    n_shards = mesh.shape["model"]
    batch_axes = rules.map.get("batch")
    if batch_axes is None:
        x_spec = jax.sharding.PartitionSpec(None, None, None)
    else:
        x_spec = jax.sharding.PartitionSpec(batch_axes, None, None)

    E = mo.num_experts
    e_l = E // n_shards

    router_spec = jax.sharding.PartitionSpec(None, None)
    w_spec = jax.sharding.PartitionSpec("model", None, None)

    def shard_fn(xl, router, wg, wu, wd):
        sid = jax.lax.axis_index("model")
        out, aux = _moe_shard(cfg, xl, router, wg, wu, wd, sid, e_l)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        return out, aux

    out, aux = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(x_spec, router_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, jax.sharding.PartitionSpec()),
        check_vma=False,
    )(x, params["router"].astype(dt), params["w_gate"].astype(dt),
      params["w_up"].astype(dt), params["w_down"].astype(dt))

    out = rules.constrain(out, ("batch", None, None))
    if mo.num_shared:
        out = out + _shared(params, x, dt, rules)
    return out, aux.astype(jnp.float32)


def _moe_shard(cfg, xl, router, wg, wu, wd, shard_id, e_l):
    """Per-device: route local tokens, gather mine, compute, scatter-add."""
    mo = cfg.moe
    dt = xl.dtype
    Bl, S, D = xl.shape
    T = Bl * S
    xt = xl.reshape(T, D)

    gates, sel, aux = route(mo, xt, router)                     # (T,k)

    C = _capacity(cfg, max(T, 4))
    e0 = shard_id * e_l
    # (T*k,) flattened assignments; keep only my experts
    sel_f = sel.reshape(-1)
    gate_f = gates.reshape(-1).astype(jnp.float32)
    tok_f = jnp.arange(sel_f.shape[0], dtype=jnp.int32) // mo.top_k
    local_e = sel_f - e0
    mine = (local_e >= 0) & (local_e < e_l)
    local_e = jnp.where(mine, local_e, e_l)      # park strangers in slot e_l

    # slot position within each local expert's queue (stable order)
    oh = jax.nn.one_hot(local_e, e_l + 1, dtype=jnp.float32)   # (T*k, e_l+1)
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1).astype(jnp.int32) - 1
    keep = mine & (pos >= 0) & (pos < C)
    slot = jnp.where(keep, local_e * C + pos, e_l * C)         # overflow bin

    # gather tokens into (e_l*C+1, D); last row is the trash bin
    h = jnp.zeros((e_l * C + 1, D), dt).at[slot].set(
        jnp.where(keep[:, None], xt[tok_f], 0))
    src_tok = jnp.zeros((e_l * C + 1,), jnp.int32).at[slot].set(
        jnp.where(keep, tok_f, 0))
    src_gate = jnp.zeros((e_l * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, gate_f, 0.0))

    he = h[:e_l * C].reshape(e_l, C, D)
    g = jnp.einsum("ecd,edf->ecf", he, wg)
    u = jnp.einsum("ecd,edf->ecf", he, wu)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)     # (e_l,C,D)
    y = (y.reshape(e_l * C, D)
         * src_gate[:e_l * C, None].astype(dt))

    out = jnp.zeros((T, D), dt).at[src_tok[:e_l * C]].add(y)
    return out.reshape(Bl, S, D), aux


def _moe_local(cfg, params, x, rules, n_shards, shard_id):
    dt = x.dtype
    out, aux = _moe_shard(cfg, x, params["router"].astype(dt),
                          params["w_gate"].astype(dt),
                          params["w_up"].astype(dt),
                          params["w_down"].astype(dt), shard_id,
                          cfg.moe.num_experts // n_shards)
    if cfg.moe.num_shared:
        out = out + _shared(params, x, dt, rules)
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# ST program: the combine as an aggregated-put access epoch
# ---------------------------------------------------------------------------

def _tiny_moe_cfg(experts, top_k, expert_ff):
    """cfg duck-type for the self-contained (benchmark / device-free)
    path; ``moe_a2a_st`` passes a real ModelConfig instead."""
    return SimpleNamespace(moe=MoEConfig(
        num_experts=experts, top_k=top_k, expert_ff=expert_ff))


def make_moe_a2a_kernels(cfg, axis, n_shards):
    """Kernel closures: the local gather/expert/scatter compute producing
    this shard's partial, and the combine summing all received partials
    (the psum replacement). Buffers carry the shard_map rank dim R=1."""
    e_l = cfg.moe.num_experts // n_shards

    def moe_shard(x, router, wg, wu, wd):
        sid = jax.lax.axis_index(axis)
        out, aux = _moe_shard(cfg, x[0], router[0], wg[0], wu[0], wd[0],
                              sid, e_l)
        return out[None], aux.reshape(1, 1)

    def combine(partial, paux, *recvs):
        # recvs = peer partials then peer aux partials
        k = len(recvs) // 2
        out = partial
        for r in recvs[:k]:
            out = out + r
        aux = paux
        for r in recvs[k:]:
            aux = aux + r
        return out, aux / n_shards

    return {"moe_shard": moe_shard, "combine": combine}


def create_a2a_window(stream, *, batch, seq, d_model, expert_ff, e_l,
                      dtype=jnp.float32, name="a2a", double_buffer=False,
                      ranks_per_node=None):
    """Window with the (replicated) token block, this shard's expert
    weights, the partial-output/aux buffers, and one recv buffer per
    peer shift of the aggregated-put combine. ``double_buffer`` ping/
    pongs the partial/aux sources AND the recv landing zones (plus the
    counters) so layer e+1's expert compute and puts never touch the
    buffers layer e's combine is still reading."""
    n = stream.grid_shape[0]
    tok = (batch, seq, d_model)
    bufs = {"x": (tok, dtype),
            "router": ((d_model, e_l * n), dtype),
            "wg": ((e_l, d_model, expert_ff), dtype),
            "wu": ((e_l, d_model, expert_ff), dtype),
            "wd": ((e_l, expert_ff, d_model), dtype),
            "partial": (tok, dtype), "paux": ((1,), jnp.float32),
            "out": (tok, dtype), "aux": ((1,), jnp.float32)}
    db_names = ["partial", "paux"]
    for k in range(1, n):
        bufs[f"recvp{k}"] = (tok, dtype)
        bufs[f"recva{k}"] = ((1,), jnp.float32)
        db_names += [f"recvp{k}", f"recva{k}"]
    topo = shifts_topology(n, stream.grid_axes,
                           ranks_per_node=ranks_per_node)
    return stream.create_window(name, bufs, list(topo.group), topology=topo,
                                double_buffer=double_buffer,
                                db_names=db_names)


@register_pattern("a2a", grid_axes=("model",), default_grid=(2,),
                  doc="expert-parallel MoE combine as aggregated puts")
def build_moe_a2a_program(stream, niter, *, cfg=None, batch=1, seq=8,
                          d_model=16, expert_ff=16, experts=None, top_k=2,
                          dtype=jnp.float32, merged=True, host_sync_every=0,
                          kernels=None, name="a2a", double_buffer=False,
                          ranks_per_node=None, **_kw):
    """Enqueue ``niter`` expert-parallel MoE layers: post -> local
    gather/expert/scatter kernel -> start -> an aggregated put of the
    partial output (+ aux) to EVERY peer shift -> complete -> wait ->
    combine kernel. ``merged`` is schedule-level (signal fusion).
    ``double_buffer`` alternates layers over ping/pong partial/recv sets.
    Returns (window, kernels)."""
    stream.pattern = stream.pattern or "a2a"
    n = stream.grid_shape[0]
    if cfg is None:
        experts = experts if experts is not None else 2 * n
        cfg = _tiny_moe_cfg(experts, top_k, expert_ff)
    else:
        d_model = cfg.d_model
        expert_ff = cfg.moe.expert_ff
    if cfg.moe.num_experts % n:
        raise ValueError(f"num_experts={cfg.moe.num_experts} must divide "
                         f"over {n} shards")
    e_l = cfg.moe.num_experts // n
    win = create_a2a_window(stream, batch=batch, seq=seq, d_model=d_model,
                            expert_ff=expert_ff, e_l=e_l, dtype=dtype,
                            name=name, double_buffer=double_buffer,
                            ranks_per_node=ranks_per_node)
    kernels = kernels or make_moe_a2a_kernels(cfg, stream.grid_axes[0], n)
    for it in range(niter):
        phase = it % 2 if double_buffer else 0

        def q(b, _p=phase):
            return win.qual(b, _p)

        recvp = [q(f"recvp{k}") for k in range(1, n)]
        recva = [q(f"recva{k}") for k in range(1, n)]
        stream.post(win, phase=phase)
        stream.launch(kernels["moe_shard"],
                      [q("x"), q("router"), q("wg"), q("wu"), q("wd")],
                      [q("partial"), q("paux")], label="moe_shard")
        stream.start(win, phase=phase)
        for k in range(1, n):
            stream.put(win, q("partial"), q(f"recvp{k}"), (k,), phase=phase)
            stream.put(win, q("paux"), q(f"recva{k}"), (k,), phase=phase)
        stream.complete(win, phase=phase)
        stream.wait(win, phase=phase)
        stream.launch(kernels["combine"],
                      [q("partial"), q("paux")] + recvp + recva,
                      [q("out"), q("aux")], label="combine")
        if host_sync_every and (it + 1) % host_sync_every == 0 \
                and it + 1 < niter:
            stream.host_sync()
    return win, kernels


def moe_a2a_st(cfg, params, x, mesh, *, axis="model", mode="st",
               throttle="adaptive", resources=64, merged=True, rules=None,
               ranks_per_node=None, pack=False):
    """Expert-parallel MoE executed THROUGH the ST pipeline (lower ->
    schedule -> compiled/host backend): the psum combine becomes the
    aggregated-put access epoch. Numerically equivalent to
    :func:`moe_a2a` on a pure expert-parallel mesh. x: (B,S,D).
    ``ranks_per_node``/``pack`` select the multi-node topology and
    materialized put aggregation: each shift's partial+aux pair rides
    ONE packed multi-buffer descriptor instead of two puts."""
    from repro.core.stream import STStream

    dt = x.dtype
    B, S, D = x.shape
    n = mesh.shape[axis]
    e_l = cfg.moe.num_experts // n
    F = cfg.moe.expert_ff
    stream = STStream(mesh, (axis,))
    win, _ = build_moe_a2a_program(stream, 1, cfg=cfg, batch=B, seq=S,
                                   dtype=dt,
                                   ranks_per_node=ranks_per_node)
    state = stream.allocate()
    fills = {
        # tokens + router replicated; each shard owns its experts' slice
        "x": jnp.broadcast_to(x[None], (n, B, S, D)),
        "router": jnp.broadcast_to(params["router"].astype(dt)[None],
                                   (n, D, e_l * n)),
        "wg": params["w_gate"].astype(dt).reshape(n, e_l, D, F),
        "wu": params["w_up"].astype(dt).reshape(n, e_l, D, F),
        "wd": params["w_down"].astype(dt).reshape(n, e_l, F, D),
    }
    for nm, val in fills.items():
        key = win.qual(nm)
        state[key] = jax.device_put(val, state[key].sharding)
    state = stream.synchronize(state, mode=mode, throttle=throttle,
                               resources=resources, merged=merged,
                               donate=False, pack=pack)
    out = state[win.qual("out")][0]           # every rank holds the sum
    aux = state[win.qual("aux")][0, 0]
    if cfg.moe.num_shared:
        if rules is None:
            from repro.sharding.rules import make_rules
            rules = make_rules(cfg, None, None)
        out = out + _shared(params, x, dt, rules)
    return out, aux.astype(jnp.float32)
