"""Per-arch smoke tests: REDUCED config of the same family, one forward +
one train step on CPU, asserting output shapes and no NaNs (deliverable f).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (cache_specs, forward, logits_from_hidden,
                          model_specs)
from repro.models.params import init_params as init_p
from repro.optim import opt_init_specs
from repro.sharding.rules import make_rules
from repro.train.steps import make_train_step


def _batch_for(cfg, B, S):
    batch = {"positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                           (B, S)),
             "targets": jnp.ones((B, S), jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.full((B, S, cfg.vision.raw_dim), 0.1,
                                   jnp.float32)
    else:
        batch["tokens"] = (jnp.arange(B * S, dtype=jnp.int32)
                           .reshape(B, S) % cfg.vocab_size)
    if cfg.family == "vlm":
        batch["vision"] = jnp.full(
            (B, cfg.vision.num_tokens, cfg.vision.raw_dim), 0.1, jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nan(arch):
    cfg = get_config(arch).reduced()
    rules = make_rules(cfg, None, None)
    params = init_p(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = _batch_for(cfg, B, S)
    x, _, aux, _ = forward(cfg, params, batch, rules=rules, moe_impl="dense")
    assert x.shape == (B, S, cfg.d_model)
    logits = logits_from_hidden(cfg, params, x, rules)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nan(arch):
    cfg = get_config(arch).reduced()
    rules = make_rules(cfg, None, None)
    specs = model_specs(cfg)
    params = init_p(specs, jax.random.PRNGKey(0))
    opt = init_p(opt_init_specs(cfg, specs), jax.random.PRNGKey(1),
                 dtype=None)
    step = make_train_step(cfg, rules, moe_impl="dense",
                           schedule=lambda s: 1e-3)
    batch = _batch_for(cfg, 2, 32)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_opt["count"]) == 1
    # at least one param changed
    changed = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params)[:5],
                        jax.tree.leaves(new_params)[:5]))
    assert changed


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_shapes(arch):
    cfg = get_config(arch).reduced()
    rules = make_rules(cfg, None, None)
    params = init_p(model_specs(cfg), jax.random.PRNGKey(0))
    B = 2
    cache = init_p(cache_specs(cfg, B, 16), jax.random.PRNGKey(1), dtype=None)
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
             "positions": jnp.zeros((B, 1), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision"] = jnp.full(
            (B, cfg.vision.num_tokens, cfg.vision.raw_dim), 0.1, jnp.float32)
    x, ncache, _, _ = forward(cfg, params, batch, rules=rules, cache=cache,
                              moe_impl="dense")
    logits = logits_from_hidden(cfg, params, x, rules, last_only=True)
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all()
    assert jax.tree.structure(ncache) == jax.tree.structure(cache)
