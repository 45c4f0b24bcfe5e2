"""Seeded random weights of a dense decoder, made on the device in one
jitted call, in bf16 and in the parameter layout the program serves.

The benchmark makes the weights itself, so that the plain reference
(``bench/references/``) and the program read the same numbers and the
reference takes nothing that the program made.  The layout (key names
and shapes) is the program's interface: embeddings ``(V, D)``, per-layer
``attn`` q/k/v/o and ``ffn`` gate/up/down linears as ``{"w": (in, out)
[, "b"]}``, RMSNorm ``{"scale"}``, and ``lm_head`` unless the embeddings
are tied.

Linear weights are normal with standard deviation ``fan_in ** -0.5``,
embeddings ``0.02``, biases ``0.02``, norm scales ``1 + 0.1 * normal``
(so that a norm applied without its scale shows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16


def dims(model: dict) -> dict:
    """Derived sizes of a configuration's ``model`` block."""
    D = model["hidden_size"]
    H = model["num_attention_heads"]
    dh = model.get("head_dim", D // H)
    return {"D": D, "H": H, "Hkv": model["num_key_value_heads"], "dh": dh,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"]}


def _linear(key, din, dout, bias):
    kw, kb = jax.random.split(key)
    p = {"w": (jax.random.normal(kw, (din, dout), jnp.float32)
               * din ** -0.5).astype(BF16)}
    if bias:
        p["b"] = (jax.random.normal(kb, (dout,), jnp.float32)
                  * 0.02).astype(BF16)
    return p


def _norm(key, d):
    return {"scale": 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)}


def _build(key, model: dict) -> dict:
    d = dims(model)
    D, F = d["D"], d["F"]
    qd, kvd = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    bias = bool(model.get("attention_bias", False))
    keys = jax.random.split(key, d["L"] + 3)
    layers = []
    for i in range(d["L"]):
        k = jax.random.split(keys[i], 9)
        layers.append({
            "attn_norm": _norm(k[0], D),
            "attn": {"q": _linear(k[1], D, qd, bias),
                     "k": _linear(k[2], D, kvd, bias),
                     "v": _linear(k[3], D, kvd, bias),
                     "o": _linear(k[4], qd, D, False)},
            "ffn_norm": _norm(k[5], D),
            "ffn": {"gate": _linear(k[6], D, F, False),
                    "up": _linear(k[7], D, F, False),
                    "down": _linear(k[8], F, D, False)},
        })
    params = {
        "embed": {"w": (jax.random.normal(keys[-1], (d["V"], D), jnp.float32)
                        * 0.02).astype(BF16)},
        "layers": layers,
        "final_norm": _norm(keys[-2], D),
    }
    if not model.get("tie_word_embeddings", False):
        params["lm_head"] = _linear(keys[-3], D, d["V"], False)
    return params


def make_weights(model: dict, seed: int) -> dict:
    """All weights of ``model`` from ``seed``, on the default device."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    fn = jax.jit(lambda k: _build(k, model))
    return jax.block_until_ready(fn(key))
