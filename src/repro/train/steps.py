"""Step builders: train (grad-accum microbatching, ZeRO-sharded optimizer),
prefill, decode. All steps are pure functions suitable for jax.jit with
in/out shardings derived from the ParamSpec trees.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import (cache_specs, forward, logits_from_hidden,
                          model_specs)
from repro.models.model import cast_big_params, lm_loss_fused
from repro.models.params import is_spec, param_pspecs
from repro.optim import cosine_schedule, opt_update


def _loss_fn(cfg, rules, moe_impl, unroll, params, mbatch):
    params = cast_big_params(cfg, params, rules)
    x, _, aux, _ = forward(cfg, params, mbatch, rules=rules,
                           moe_impl=moe_impl, unroll=unroll)
    loss = lm_loss_fused(cfg, params, x, mbatch["targets"], rules)
    return loss + aux, (loss, aux)


def effective_accum(cfg, rules, global_batch=None) -> int:
    """Clamp grad_accum so each microbatch still covers every batch shard
    (a microbatch smaller than the batch-sharding degree idles devices and
    cannot even be sharded as a pjit argument)."""
    accum = max(cfg.grad_accum, 1)
    if not global_batch or rules.mesh is None:
        return accum
    ba = rules.map.get("batch")
    if not ba:
        return accum
    shard = 1
    for a in (ba if isinstance(ba, tuple) else (ba,)):
        shard *= rules.mesh.shape[a]
    accum = min(accum, max(1, global_batch // shard))
    while accum > 1 and (global_batch % accum
                         or (global_batch // accum) % shard):
        accum -= 1
    return accum


def make_train_step(cfg, rules, moe_impl: str = "gshard",
                    schedule=cosine_schedule, unroll: bool = False,
                    global_batch=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch leaves have global-batch leading dim; grad accumulation reshapes to
    (accum, micro, ...) and scans, accumulating grads in opt_state_dtype
    (bf16 for the very large archs — the f32 master params absorb rounding).
    """
    specs = model_specs(cfg)
    pspecs = param_pspecs(specs, rules)
    accum = effective_accum(cfg, rules, global_batch)
    acc_dtype = (jnp.bfloat16 if jnp.dtype(cfg.opt_state_dtype) == jnp.bfloat16
                 else jnp.float32)
    loss_fn = functools.partial(_loss_fn, cfg, rules, moe_impl, unroll)

    def constrain_grads(g):
        if rules.mesh is None:
            return g
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(rules.mesh, s)),
            g, pspecs)

    def train_step(params, opt_state, batch):
        step = opt_state["count"]
        if accum == 1:
            (tot, (loss, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = constrain_grads(grads)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
                batch)
            gzero = constrain_grads(jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params))

            def micro(carry, m):
                gsum, lsum, asum = carry
                (tot, (loss, aux)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, m)
                gsum = constrain_grads(jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g))
                return (gsum, lsum + loss, asum + aux), None

            (gsum, lsum, asum), _ = jax.lax.scan(
                micro, (gzero, jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32)),
                mb)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss, aux = lsum / accum, asum / accum

        lr = schedule(step)
        new_params, new_opt = opt_update(cfg, params, grads, opt_state, lr)
        metrics = {"loss": loss, "aux_loss": aux, "lr": lr,
                   "step": new_opt["count"]}
        return new_params, new_opt, metrics

    return train_step


def init_cache_in_jit(cfg, batch: int, max_len: int, rules,
                      cache_dtype=jnp.bfloat16):
    """Create a zeroed, sharding-constrained cache inside a jitted fn."""
    cspecs = cache_specs(cfg, batch, max_len, cache_dtype)

    def mk(s):
        z = jnp.zeros(s.shape, s.dtype)
        return rules.constrain(z, s.axes)

    return jax.tree.map(mk, cspecs, is_leaf=is_spec)


def _prefill_body(cfg, rules, max_len, moe_impl, unroll):
    """prefill(params, batch) -> (last_logits, cache, routes): the one
    prefill body of the prefill steps (``routes`` as ``forward`` gives
    them)."""

    def prefill(params, batch):
        B, S = batch["positions"].shape
        cache = init_cache_in_jit(cfg, B, max_len or S, rules)
        x, new_cache, _, routes = forward(cfg, params, batch, rules=rules,
                                          cache=cache, moe_impl=moe_impl,
                                          unroll=unroll)
        logits = logits_from_hidden(cfg, params, x, rules, last_only=True)
        return logits, new_cache, routes

    return prefill


def _decode_body(cfg, rules, moe_impl, unroll):
    """decode(params, batch, cache) -> (logits (B,1,V), hidden (B,1,D),
    new_cache, routes): the one decode body of the decode steps."""

    def decode(params, batch, cache):
        x, new_cache, _, routes = forward(cfg, params, batch, rules=rules,
                                          cache=cache, moe_impl=moe_impl,
                                          unroll=unroll)
        logits = logits_from_hidden(cfg, params, x, rules, last_only=True)
        return logits, x, new_cache, routes

    return decode


def make_prefill_step(cfg, rules, max_len: Optional[int] = None,
                      moe_impl: str = "gshard", unroll: bool = False):
    """prefill_step(params, batch) -> (last_logits, cache)."""
    body = _prefill_body(cfg, rules, max_len, moe_impl, unroll)

    def prefill_step(params, batch):
        return body(params, batch)[:2]

    return prefill_step


def make_decode_step(cfg, rules, moe_impl: str = "gshard",
                     unroll: bool = False):
    """decode_step(params, batch, cache) -> (logits (B,1,V), new_cache)."""
    body = _decode_body(cfg, rules, moe_impl, unroll)

    def decode_step(params, batch, cache):
        logits, _, new_cache, _ = body(params, batch, cache)
        return logits, new_cache

    return decode_step


def _greedy_ids(cfg, logits):
    """(B, 1, V) last-position logits -> (B,) greedy token ids. The
    argmax runs device-side so serving transfers B int32 ids per step
    instead of the full (B, 1, vocab) logits array."""
    return jnp.argmax(logits[:, -1, :cfg.vocab_size],
                      axis=-1).astype(jnp.int32)


def _routing(cfg, routes, batch) -> tuple:
    """A MoE model's step results past the tokens and cache, () for
    another: the load, (MoE layers, held experts) int32 assignments each
    held expert received from the batch's live rows (``batch["live"]``,
    (B,) bool, marks the slots that serve a request; every row counts
    where it is absent), and the experts each token chose, (B * S, MoE
    layers * top_k) in (batch, position) and (layer, choice) order, as
    uint8 where the experts fit it: two wide axes, so that the device
    pads a decode step's few bytes to few more."""
    if not routes:
        return ()
    sel = routes["sel"]                                  # (L, B, S, K)
    first, n = cfg.moe.held_range
    hit = sel[..., None] == first + jnp.arange(n, dtype=sel.dtype)
    if "live" in batch:
        hit = hit & batch["live"][None, :, None, None, None]
    load = jnp.sum(hit, axis=(1, 2, 3), dtype=jnp.int32)
    L, B, S, K = sel.shape
    sel = jnp.transpose(sel, (1, 2, 0, 3)).reshape(B * S, L * K)
    return load, sel.astype(jnp.uint8 if cfg.moe.num_experts <= 256
                            else jnp.int32)


def make_prefill_sample_step(cfg, rules, max_len: Optional[int] = None,
                             moe_impl: str = "gshard",
                             unroll: bool = False):
    """prefill_sample_step(params, batch) -> (ids (B,), cache): prefill
    plus device-side greedy sampling of each slot's first token. A MoE
    model's step also returns its load and chosen experts
    (:func:`_routing`)."""
    body = _prefill_body(cfg, rules, max_len, moe_impl, unroll)

    def prefill_sample_step(params, batch):
        logits, cache, routes = body(params, batch)
        return (_greedy_ids(cfg, logits), cache,
                *_routing(cfg, routes, batch))

    return prefill_sample_step


def make_decode_sample_step(cfg, rules, moe_impl: str = "gshard",
                            unroll: bool = False):
    """decode_sample_step(params, batch, cache) -> (ids (B,), hid (B, D),
    new_cache): one decode step plus device-side greedy sampling. The
    last-position hidden block rides along as the MoE-dispatch payload
    of the ST serving path (ignored by the baseline). A MoE model's step
    also returns its load and chosen experts, as the prefill step does."""
    body = _decode_body(cfg, rules, moe_impl, unroll)

    def decode_sample_step(params, batch, cache):
        logits, x, new_cache, routes = body(params, batch, cache)
        return (_greedy_ids(cfg, logits), x[:, -1, :], new_cache,
                *_routing(cfg, routes, batch))

    return decode_sample_step
