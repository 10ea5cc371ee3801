"""Plain float32 forward of an MLA + MoE decoder at DeepSeek-V3's block
(Moonlight-16B-A3B), for the held share of the routed experts.

Written from the published architecture (DeepSeek-V3's modeling code as
the model's ``config.json`` configures it) in straightforward
``jax.numpy`` at ``default_matmul_precision("highest")``: no kernels, no
cache, no absorbed decode, no grouped matmul. Per layer:

  * RMSNorm, then multi-head latent attention in its expanded form: the
    query straight from the input (no query latent), the key/value
    latent normed and expanded per head, the rope dims rotated in
    adjacent pairs (each pair a complex number times e^(i pos theta_j)),
    softmax over causal scores scaled by 1/sqrt(nope + rope);
  * RMSNorm, then layer 0's dense SwiGLU, or the MoE: float32 logits,
    sigmoid scores, the top-k of scores + selection bias, weights the
    chosen scores over their sum times ``routed_scaling_factor``; every
    held expert runs on every token, weighted by a one-hot of the
    choice (0 where it was not chosen), plus the shared experts.

Then the final RMSNorm and the untied output head.

The share: only the experts of ``experts_held`` are computed; what the
other chips' experts add is left out, as the program leaves it out. It
imports nothing of the program and makes its own weights from the seed
(``bench.weights_mla_moe``), rounded to the dtype the configuration
serves them in and then held in float32.

It runs one layer at a time, making that layer's weights inside the
jitted call, and a few sequences at a time, so it fits on the chip
beside nothing else. It can follow given expert sets (``follow``): each
MoE layer then weighs and runs the experts it is given, at its own
float32 scores, in place of its own choice. For the route check it
gives, at every position fed and MoE layer, its own chosen set and the
margin between its k-th and (k+1)-th choice values.

``quant="fp8"`` is the control: the same forward computed in fp8
(e4m3), the step below bf16: every matrix rounded per output channel
and every matrix input per token, each scaled to the format's range.
The router's logits stay in float32, as published.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_mla_moe as W

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _cfg(c: dict) -> tuple:
    """The hashable sizes and constants a layer needs."""
    k = W.dims(c)
    first, count = W.held(c)
    return (tuple(sorted(k.items())), first, count,
            int(c["num_experts_per_tok"]), float(c["routed_scaling_factor"]),
            float(c["rope_theta"]), float(c["rms_norm_eps"]),
            c["serving"]["weights_dtype"])


def _fp8(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _act(x, quant):
    return _fp8(x, (-1,)) if quant == "fp8" else x


def _round(w, wdt, quant, axes=(0,)):
    w = w.astype(_DTYPES[wdt]).astype(jnp.float32)
    return _fp8(w, axes) if quant == "fp8" else w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope_pairs(x, pos, theta):
    """x (B, S, ..., r), pos (S,): dims (2j, 2j+1) rotated together by
    the angle pos * theta^(-2j/r), as a complex number (a + ib) times
    e^(i angle)."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (S, r/2)
    ang = ang.reshape((1, ang.shape[0]) + (1,) * (x.ndim - 3)
                      + (ang.shape[1],))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _swiglu(x, wg, wu, wd, quant):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return _act(h, quant) @ wd


@partial(jax.jit, static_argnums=(0, 3, 6))
def _layer(cfg, key, layer, moe, x, lengths, quant, follow=None):
    """One block over x (B, S, d): (x, chosen sets (B, S, k), margins
    (B, S)); the last two zero for the dense layer. With ``follow``
    (B, S, k) the experts run are those, not the chosen ones."""
    k, first, count, top_k, scale, theta, eps, wdt = cfg
    k = dict(k)
    shapes = {**W.mixer_shapes(k), **(W.moe_shapes(k) if moe
                                       else W.dense_shapes(k))}

    def w(name, axes=(0,)):
        v = W.leaf(key, name, layer, shapes[name])
        if name == "ffn.router_bias":       # held in float32
            return v
        if v.ndim == 1:                     # norm scales: not quantized
            return v.astype(_DTYPES[wdt]).astype(jnp.float32)
        return _round(v, wdt, quant, axes)

    B, S, d = x.shape
    H, nope, rope, r = k["h"], k["nope"], k["rope"], k["kv_lora"]
    pos = jnp.arange(S)
    hn = _act(_rmsnorm(x, w("norm1.scale"), eps), quant)
    if k["q_lora"]:
        cq = _act(_rmsnorm(hn @ w("mixer.wq_a"), w("mixer.q_norm"), eps),
                  quant)
        q = jnp.einsum("bsl,lhk->bshk", cq, w("mixer.wq_b"))
    else:
        q = jnp.einsum("bsd,dhk->bshk", hn, w("mixer.wq"))
    kv = hn @ w("mixer.wkv_a")                               # (B,S,r+rope)
    lat = _act(_rmsnorm(kv[..., :r], w("mixer.kv_norm"), eps), quant)
    k_pe = _rope_pairs(kv[..., r:], pos, theta)              # (B,S,rope)
    q_pe = _rope_pairs(q[..., nope:], pos, theta)
    k_nope = jnp.einsum("bsl,lhk->bshk", lat, w("mixer.wk_b"))
    v = jnp.einsum("bsl,lhk->bshk", lat, w("mixer.wv_b"))
    scores = (jnp.einsum("bshk,bthk->bhst", q[..., :nope], k_nope)
              + jnp.einsum("bshk,btk->bhst", q_pe, k_pe)) \
        / jnp.sqrt(jnp.float32(nope + rope))
    mask = (pos[None, :] <= pos[:, None])[None, None] \
        & (pos < lengths[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    att = _act(jnp.einsum("bhst,bthv->bshv", p, v).reshape(B, S, -1),
               quant).reshape(B, S, H, k["v"])
    x = x + jnp.einsum("bshv,hvd->bsd", att, w("mixer.wo", (0, 1)))

    hn = _act(_rmsnorm(x, w("norm2.scale"), eps), quant)
    if not moe:
        y = _swiglu(hn, w("ffn.w_gate"), w("ffn.w_up"), w("ffn.w_down"),
                    quant)
        return x + y, jnp.zeros((B, S, top_k), jnp.int32), \
            jnp.zeros((B, S), jnp.float32)
    router = W.leaf(key, "ffn.router", layer, shapes["ffn.router"]) \
        .astype(_DTYPES[wdt]).astype(jnp.float32)
    scores = jax.nn.sigmoid(hn @ router)                     # (B,S,E)
    choice = scores + w("ffn.router_bias")
    top, sel = jax.lax.top_k(choice, top_k + 1)
    margin = top[..., top_k - 1] - top[..., top_k]
    sel = sel[..., :top_k]
    run = sel if follow is None else follow
    chosen = jnp.take_along_axis(scores, run, -1)
    gate = chosen / chosen.sum(-1, keepdims=True) * scale    # (B,S,k)
    y = _swiglu(hn, w("ffn.shared.w_gate"), w("ffn.shared.w_up"),
                w("ffn.shared.w_down"), quant)
    for j in range(count):
        e = first + j
        g = jnp.sum(jnp.where(run == e, gate, 0.0), -1)      # one-hot weight

        def ex(name, axes=(0,)):
            v = W.leaf(key, name, layer, shapes[name], e)
            return _round(v, wdt, quant, axes)
        y = y + g[..., None] * _swiglu(hn, ex("ffn.w_gate"), ex("ffn.w_up"),
                                       ex("ffn.w_down"), quant)
    return x + y, sel.astype(jnp.int32), margin


@partial(jax.jit, static_argnums=(0, 2, 3))
def _embedding(cfg, key, wdt, quant):
    k = dict(cfg[0])
    t = W.table(key, "embed.tok", k["vocab"], k["d"], W.EMBED_STD)
    t = t.astype(_DTYPES[wdt]).astype(jnp.float32)
    return _fp8(t, (1,)) if quant == "fp8" else t


def hidden_states(c: dict, seed: int, seqs: Sequence[np.ndarray],
                  positions: Sequence[np.ndarray],
                  quant: Optional[str] = None, rows: int = 1,
                  pad_to: int = 512, routes: bool = False,
                  follow: Optional[Sequence[np.ndarray]] = None):
    """Final-normed hidden states (M, d), float32, at the ``positions``
    of each sequence, in order. ``seqs[i]`` holds the token ids fed at
    positions 0.. The sequences go through ``rows`` at a time, each
    group padded to a multiple of ``pad_to`` positions (one length for
    every group compiles each layer kind once). ``follow[i]``, (len of
    ``seqs[i]``, MoE layers, k), gives the experts to run at each
    position of sequence ``i``. With ``routes`` also, per sequence, the
    experts the reference chose at each position fed, (len, MoE layers,
    k), and the margins between its k-th and (k+1)-th choice values,
    (len, MoE layers)."""
    out = []
    for i in range(0, len(seqs), rows):
        out.append(_hidden_block(c, seed, seqs[i:i + rows],
                                 positions[i:i + rows], quant, pad_to,
                                 follow[i:i + rows] if follow is not None
                                 else None))
    hid = np.concatenate([o[0] for o in out])
    if not routes:
        return hid
    return (hid, [s for o in out for s in o[1]],
            [m for o in out for m in o[2]])


def _hidden_block(c, seed, seqs, positions, quant, pad_to, follow):
    cfg = _cfg(c)
    key = W.seed_key(seed)
    lengths = np.array([len(s) for s in seqs], np.int32)
    S = int(-(-lengths.max() // pad_to) * pad_to)
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    if follow is not None:          # (MoE layers, rows, S, k)
        fol = np.zeros((follow[0].shape[1], len(seqs), S, follow[0].shape[2]),
                       np.int32)
        for i, f in enumerate(follow):
            fol[:, i, :len(f)] = np.asarray(f).transpose(1, 0, 2)
    rows = np.concatenate([np.full(len(p), i) for i, p in
                           enumerate(positions)])
    cols = np.concatenate([np.asarray(p) for p in positions])
    sels, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = _embedding(cfg, key, cfg[-1], quant)[jnp.asarray(toks)]
        first_dense = int(c["first_k_dense_replace"])
        for layer in range(c["num_hidden_layers"]):
            moe = layer >= first_dense
            f = None
            if moe and follow is not None:
                f = jnp.asarray(fol[layer - first_dense])
            x, sel, margin = _layer(cfg, key, layer, moe, x,
                                    jnp.asarray(lengths), quant, f)
            if moe:
                sels.append(np.asarray(sel))
                margins.append(np.asarray(margin))
        fin = W.leaf(key, "final_norm.scale", 0, (x.shape[-1],)) \
            .astype(_DTYPES[cfg[-1]]).astype(jnp.float32)
        x = _rmsnorm(x, fin, cfg[6])
        hid = np.asarray(x[jnp.asarray(rows), jnp.asarray(cols)])
    sels, margins = np.stack(sels, 2), np.stack(margins, 2)
    return (hid, [sels[i, :n] for i, n in enumerate(lengths)],
            [margins[i, :n] for i, n in enumerate(lengths)])


@partial(jax.jit, static_argnums=(0, 3, 5))
def _head_chunk(cfg, head, hid, want_argmax, tokens, quant):
    logits = _act(hid, quant) @ head.T                       # (m, V)
    if want_argmax:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    served = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=-1) - served


@partial(jax.jit, static_argnums=(0, 2, 3))
def _head_table(cfg, key, wdt, quant):
    """(vocab, d) output head, float32."""
    k = dict(cfg[0])
    t = W.table(key, "embed.unembed", k["vocab"], k["d"],
                1.0 / np.sqrt(k["d"]))
    t = t.astype(_DTYPES[wdt]).astype(jnp.float32)
    return _fp8(t, (1,)) if quant == "fp8" else t


def _head(c, seed, hidden, tokens, want_argmax, quant, chunk=256):
    cfg = _cfg(c)
    key = W.seed_key(seed)
    M = hidden.shape[0]
    pad = -M % chunk
    hid = np.concatenate([hidden, np.zeros((pad, hidden.shape[1]),
                                           np.float32)])
    tok = np.concatenate([np.asarray(tokens, np.int32),
                          np.zeros(pad, np.int32)])
    out: List[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        head = _head_table(cfg, key, cfg[-1], quant)
        for i in range(0, M + pad, chunk):
            out.append(np.asarray(_head_chunk(
                cfg, head, jnp.asarray(hid[i:i + chunk]), want_argmax,
                jnp.asarray(tok[i:i + chunk]), quant)))
    return np.concatenate(out)[:M]


def logit_gaps(c: dict, seed: int, hidden: np.ndarray,
               tokens: Sequence[int]) -> np.ndarray:
    """For each hidden state, how far the logit of ``tokens[i]`` lies
    below the best logit over the vocabulary (0 where it is the best)."""
    return _head(c, seed, hidden, tokens, False, None)


def argmax_tokens(c: dict, seed: int, hidden: np.ndarray,
                  quant: Optional[str] = None) -> np.ndarray:
    """The token each hidden state puts first."""
    return _head(c, seed, hidden, np.zeros(len(hidden), np.int32), True,
                 quant)
