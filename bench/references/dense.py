"""Plain float32 reference of a dense decoder (Llama / Qwen1.5 block).

Imports nothing of the program.  It follows the published block:

    x = embed[tokens]
    for each layer:
        h = rmsnorm(x) * scale
        q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (biases: Qwen1.5)
        rotary embedding on q and k, halves rotated (rotate_half)
        o = softmax(q k^T / sqrt(dh), causal) v, key/value heads shared
            by num_attention_heads / num_key_value_heads query heads
        x = x + o Wo
        h = rmsnorm(x) * scale
        x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = rmsnorm(x) * scale @ (embed^T if tied else Wlm_head)

in float32 with matrix products at ``highest`` precision, on the
weights as stored (bf16 values read as float32), with no cache, kernel,
quantization or batching.  It runs one sequence at a time, a layer per
jitted call and the queries in blocks, so that it fits beside the
weights once the program's state is freed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 1024          # query rows per attention block


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _lin(p, x):
    y = jnp.dot(x, p["w"].astype(F32), precision="highest")
    if "b" in p:
        y = y + p["b"].astype(F32)
    return y


def _rope(x, pos, theta):
    """x (T, H, dh), pos (T,): rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = pos.astype(F32)[:, None] * inv[None, :]               # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("H", "Hkv", "dh", "theta",
                                             "eps"))
def _layer(p, x, *, H, Hkv, dh, theta, eps):
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = _rms(x, p["attn_norm"]["scale"], eps)
    q = _rope(_lin(p["attn"]["q"], h).reshape(T, H, dh), pos, theta)
    k = _rope(_lin(p["attn"]["k"], h).reshape(T, Hkv, dh), pos, theta)
    v = _lin(p["attn"]["v"], h).reshape(T, Hkv, dh)
    G = H // Hkv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo: lo + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision="highest") / math.sqrt(dh)
        qpos = pos[lo: lo + Q_BLOCK]
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", w, v, precision="highest"))
    o = jnp.concatenate(outs, 0).reshape(T, H * dh)
    x = x + _lin(p["attn"]["o"], o)
    h = _rms(x, p["ffn_norm"]["scale"], eps)
    f = jax.nn.silu(_lin(p["ffn"]["gate"], h)) * _lin(p["ffn"]["up"], h)
    return x + _lin(p["ffn"]["down"], f)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _gaps(final_scale, head, x, rows, tokens, *, eps, tied):
    """For each of ``rows``: the best logit minus the logit of ``tokens``,
    and the logit vector's standard deviation."""
    h = _rms(x[rows], final_scale, eps)
    w = head.astype(F32)
    logits = jnp.dot(h, w.T if tied else w, precision="highest")
    best = jnp.max(logits, axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - mine, jnp.std(logits, axis=-1)


def served_gaps(params: dict, model: dict, prompt: np.ndarray,
                served: np.ndarray, pad_to: int, rows_to: int):
    """How far below the reference's best logit each served token lies.

    The sequence ``prompt + served[:-1]`` is padded at its end to
    ``pad_to`` positions (causality keeps the padding out of every real
    row) and run through the model; row ``P - 1 + j`` holds the logits
    that chose ``served[j]``.  ``rows_to`` pads the rows read (one
    compiled shape per cell).  Returns (gaps, logit standard deviations)
    as float64 arrays of ``len(served)``."""
    from bench.weights import dims
    d = dims(model)
    P, N = int(prompt.size), int(served.size)
    seq = np.zeros((pad_to,), np.int32)
    seq[:P] = prompt
    seq[P: P + N - 1] = served[:-1]
    rows = np.full((rows_to,), P - 1, np.int32)
    rows[:N] = np.arange(P - 1, P - 1 + N)
    toks = np.zeros((rows_to,), np.int32)
    toks[:N] = served
    eps = float(model["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["w"], jnp.asarray(seq))
        for lp in params["layers"]:
            x = _layer(lp, x, H=d["H"], Hkv=d["Hkv"], dh=d["dh"],
                       theta=float(model["rope_theta"]), eps=eps)
        tied = bool(model.get("tie_word_embeddings", False))
        head = params["embed"]["w"] if tied else params["lm_head"]["w"]
        gaps, spread = _gaps(params["final_norm"]["scale"], head, x,
                             jnp.asarray(rows), jnp.asarray(toks),
                             eps=eps, tied=tied)
    return (np.asarray(gaps, np.float64)[:N],
            np.asarray(spread, np.float64)[:N])
