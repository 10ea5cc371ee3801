"""Operations and bytes that an MLA + MoE decoder's step requires (the
configuration's file gives the sizes, ``bench.weights_mla_moe.dims``),
counted from its shapes and from the program's held-expert load.

As in ``bench.costs``, every count is the least the algorithm needs:
a decode step reads each weight it uses once, the latent cache of each
active slot over its context once, and writes each slot's new latent
row; it multiplies in the absorbed form (scores against the latent,
never the expanded keys and values). A held expert is counted only in a
layer where it received a token (the load), and then with the tokens
it received. The load is the program's counter, which counts the
tokens of the slots that serve a request only: an idle decode slot or
a prefill's padding row is work the program does, not work the step
requires. The embedding is a row lookup and counts no operations.
"""
from __future__ import annotations

from typing import Iterable, Sequence

from bench import weights_mla_moe as W


def _n(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out


def mla_params(k: dict) -> int:
    """Matrix parameters of one MLA block (norm scales excluded)."""
    return sum(_n(s) for name, s in W.mixer_shapes(k).items()
               if len(s) > 1)


def expert_params(k: dict) -> int:
    """Parameters of one routed expert: gate, up and down."""
    return 3 * k["d"] * k["ff"]


def layer_counts(c: dict) -> tuple:
    """(layers, dense layers, MoE layers)."""
    layers = c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    return layers, dense, layers - dense


def decode_token_flops(c: dict, context: int) -> int:
    """FLOPs of one decode token that attends over ``context`` positions
    (itself included), not counting the routed experts: every MLA block
    in its absorbed form (the query and latent projections, the key
    latent folded into the query, scores against ``kv_lora + rope`` per
    position, the context over ``kv_lora``, the value latent and the
    output), the dense layers, the router and shared experts of every
    MoE layer, and the output head."""
    k = W.dims(c)
    layers, dense, moe = layer_counts(c)
    H, r, rope = k["h"], k["kv_lora"], k["rope"]
    attn = 2 * mla_params(k) + 2 * H * context * (r + rope) \
        + 2 * H * context * r
    ffn_dense = 2 * 3 * k["d"] * k["dense_ff"]
    ffn_moe = 2 * k["d"] * k["experts"] + 2 * 3 * k["d"] * k["shared_ff"]
    return layers * attn + dense * ffn_dense + moe * ffn_moe \
        + 2 * k["d"] * k["vocab"]


def expert_work(c: dict, load: Sequence[int], itemsize: int = 2) -> tuple:
    """(FLOPs, HBM bytes) of the held experts' grouped matmul in one MoE
    layer whose held experts received ``load`` tokens each: each
    assignment through the expert's three matrices; the weights of each
    expert that received any, read once; each assignment's input row
    read and output row written."""
    k = W.dims(c)
    tokens = sum(int(x) for x in load)
    hit = sum(1 for x in load if int(x) > 0)
    flops = 2 * expert_params(k) * tokens
    nbytes = (expert_params(k) * hit + 2 * k["d"] * tokens) * itemsize
    return flops, nbytes


def decode_work(c: dict, contexts: Iterable[int],
                load: Sequence[Sequence[int]], weight_itemsize: int = 2,
                cache_itemsize: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one decode step needs for the active slots
    whose contexts (positions attended, the new one included) are
    ``contexts``, with ``load`` (MoE layers, held experts) the
    assignments each held expert received: every weight the step uses
    once (MLA, norms, dense layers, routers and selection biases, shared
    experts, the held experts that received tokens, the final norm and
    the output head), each slot's latent rows over its earlier positions
    read and its new row written."""
    ctx = [int(x) for x in contexts]
    k = W.dims(c)
    layers, dense, moe = layer_counts(c)
    d = k["d"]
    row = layers * (k["kv_lora"] + k["rope"]) * cache_itemsize
    weights = (layers * (mla_params(k) + 2 * d + k["kv_lora"])
               + dense * 3 * d * k["dense_ff"]
               + moe * (d * k["experts"] + 3 * d * k["shared_ff"])
               + d + d * k["vocab"]) * weight_itemsize \
        + moe * k["experts"] * 4            # float32 selection bias
    flops = sum(decode_token_flops(c, x) for x in ctx)
    nbytes = weights + sum(row * x for x in ctx)
    for layer_load in load:
        f, b = expert_work(c, layer_load, weight_itemsize)
        flops += f
        nbytes += b - 2 * d * sum(int(x) for x in layer_load) \
            * weight_itemsize   # rows stay on the chip within the step
    return flops, nbytes
