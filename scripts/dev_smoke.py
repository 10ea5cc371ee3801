"""Dev smoke: tiny forward/train/decode for every arch (single CPU device)."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models import (model_specs, cache_specs, forward,
                          logits_from_hidden, lm_loss, param_count)
from repro.models.params import init_params as init_p
from repro.sharding.rules import make_rules

def run(arch):
    cfg = get_config(arch).reduced()
    rules = make_rules(cfg, None, None)
    specs = model_specs(cfg)
    params = init_p(specs, jax.random.PRNGKey(0))
    B, S = 2, 64
    batch = {"positions": jnp.broadcast_to(jnp.arange(S), (B, S))}
    if cfg.family == "audio":
        batch["frames"] = jnp.ones((B, S, cfg.vision.raw_dim), jnp.float32) * 0.1
    else:
        batch["tokens"] = jnp.arange(B * S).reshape(B, S) % cfg.vocab_size
    if cfg.family == "vlm":
        batch["vision"] = jnp.ones((B, cfg.vision.num_tokens,
                                    cfg.vision.raw_dim), jnp.float32) * 0.1
    x, _, aux, _ = forward(cfg, params, batch, rules=rules, moe_impl="dense")
    logits = logits_from_hidden(cfg, params, x, rules)
    targets = jnp.zeros((B, S), jnp.int32)
    loss = lm_loss(cfg, logits, targets, rules)
    assert logits.shape == (B, S, cfg.padded_vocab), logits.shape
    assert np.isfinite(np.asarray(loss)), loss
    # decode one step
    cspecs = cache_specs(cfg, B, 32)
    cache = init_p(cspecs, jax.random.PRNGKey(1), dtype=None)
    dbatch = {"tokens": jnp.zeros((B, 1), jnp.int32),
              "positions": jnp.zeros((B, 1), jnp.int32)}
    if cfg.family == "vlm":
        dbatch["vision"] = batch["vision"]
    xd, ncache, _, _ = forward(cfg, params, dbatch, rules=rules, cache=cache,
                               moe_impl="dense")
    ld = logits_from_hidden(cfg, params, xd, rules, last_only=True)
    assert ld.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(ld)).all()
    print(f"OK {arch:24s} loss={float(loss):.3f} params={param_count(specs):,}")

if __name__ == "__main__":
    archs = sys.argv[1:] or ARCH_IDS
    for a in archs:
        run(a)
