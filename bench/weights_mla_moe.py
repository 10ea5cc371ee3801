"""Seeded weights of an MLA + MoE decoder (DeepSeek-V3's block), leaf by
leaf.

Every weight is drawn from the run's ``--seed`` by the leaf's name, its
layer and, for a routed expert, the expert's id among all the routed
experts, so the harness makes the whole tree on the device in one jitted
call, the reference makes one layer at a time with the same numbers, and
a chip that holds experts 0-7 holds the same experts as any other share
would. Neither side takes anything from the other.

Leaf names follow the program's tree below a block: ``norm1.scale``,
``mixer.wq`` (or ``mixer.wq_a`` ...), ``ffn.router``, ``ffn.w_gate`` (a
routed expert), ``ffn.shared.w_gate``; and ``embed.tok``,
``embed.unembed``, ``final_norm.scale``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from bench.weights import seed_key  # noqa: F401  (the seed's key)

# selection bias: N(0, BIAS_STD^2). The sigmoid scores of the router
# (logits ~ N(0, 1)) lie 0.01-0.05 apart around the 6th and 7th choice,
# so a bias of this size moves some selections and leaves most: a
# smaller one would leave the bias untested, a larger one route by the
# bias alone
BIAS_STD = 0.05
EMBED_STD = 0.02


def dims(c: dict) -> dict:
    """The widths the block uses, from the configuration file."""
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "q_lora": c["q_lora_rank"] or 0, "kv_lora": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "v": c["v_head_dim"], "dense_ff": c["intermediate_size"],
            "ff": c["moe_intermediate_size"],
            "shared_ff": c["moe_intermediate_size"] * c["n_shared_experts"],
            "experts": c["published_n_routed_experts"],
            "vocab": c["vocab_size"]}


def held(c: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    first, end = c["experts_held"]
    if end - first != c["n_routed_experts"]:
        raise ValueError(f"experts_held {c['experts_held']} does not hold "
                         f"n_routed_experts={c['n_routed_experts']}")
    return first, end - first


def mixer_shapes(k: dict) -> dict:
    """A block's attention leaves and norms, from :func:`dims`."""
    qk = k["nope"] + k["rope"]
    q = ({"mixer.wq_a": (k["d"], k["q_lora"]),
          "mixer.q_norm": (k["q_lora"],),
          "mixer.wq_b": (k["q_lora"], k["h"], qk)} if k["q_lora"]
         else {"mixer.wq": (k["d"], k["h"], qk)})
    return {**q, "mixer.wkv_a": (k["d"], k["kv_lora"] + k["rope"]),
            "mixer.kv_norm": (k["kv_lora"],),
            "mixer.wk_b": (k["kv_lora"], k["h"], k["nope"]),
            "mixer.wv_b": (k["kv_lora"], k["h"], k["v"]),
            "mixer.wo": (k["h"], k["v"], k["d"]),
            "norm1.scale": (k["d"],), "norm2.scale": (k["d"],)}


def dense_shapes(k: dict) -> dict:
    return {"ffn.w_gate": (k["d"], k["dense_ff"]),
            "ffn.w_up": (k["d"], k["dense_ff"]),
            "ffn.w_down": (k["dense_ff"], k["d"])}


def moe_shapes(k: dict) -> dict:
    """Per MoE layer; a routed expert's leaf is one expert's matrix."""
    return {"ffn.router": (k["d"], k["experts"]),
            "ffn.router_bias": (k["experts"],),
            "ffn.w_gate": (k["d"], k["ff"]), "ffn.w_up": (k["d"], k["ff"]),
            "ffn.w_down": (k["ff"], k["d"]),
            "ffn.shared.w_gate": (k["d"], k["shared_ff"]),
            "ffn.shared.w_up": (k["d"], k["shared_ff"]),
            "ffn.shared.w_down": (k["shared_ff"], k["d"])}


ROUTED = ("ffn.w_gate", "ffn.w_up", "ffn.w_down")


def _std(name: str, shape) -> float:
    """One over the square root of the width a matrix contracts."""
    if name == "mixer.wo":
        return 1.0 / math.sqrt(shape[0] * shape[1])
    return 1.0 / math.sqrt(shape[0])


def _normal(key, name: str, layer: int, shape, index=0) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(jax.random.fold_in(k, layer), index)
    return jax.random.normal(k, shape, jnp.float32)


def leaf(key, name: str, layer: int, shape, expert=0) -> jax.Array:
    """float32 values of one leaf of one layer (``expert``: a routed
    expert's id). Norm scales are 1 + 0.1 N(0, 1), so a scale that is
    dropped shows; the selection bias N(0, BIAS_STD^2); the rest
    N(0, std^2), std by :func:`_std`."""
    z = _normal(key, name, layer, shape, expert)
    if name.endswith((".scale", "_norm")):
        return 1.0 + 0.1 * z
    if name == "ffn.router_bias":
        return BIAS_STD * z
    return z * _std(name, shape)


def experts(key, name: str, layer: int, first: int, count: int, shape):
    """(count, *shape) float32: the held experts' leaf of one layer."""
    return jax.vmap(lambda e: leaf(key, name, layer, shape, e))(
        first + jnp.arange(count))


def table(key, name: str, rows: int, cols: int, std: float) -> jax.Array:
    """(rows, cols) float32 N(0, std^2), row by row: the embedding
    (``embed.tok``, std EMBED_STD) or the output head (``embed.unembed``
    as (vocab, d) rows, std 1/sqrt(d); the program holds its transpose)."""
    return jax.vmap(lambda r: _normal(key, name, 0, (cols,), r) * std)(
        jnp.arange(rows))
