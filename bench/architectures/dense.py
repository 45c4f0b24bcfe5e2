"""Dense decoder (Llama / Qwen1.5 block): its weights in the layout the
program serves, the map from its configuration keys to the program's
``ModelConfig``, and the shapes the roofline readers count.

A configuration file names its architecture with ``"architecture"``
(``dense`` where the key is absent), and the harness loads
``bench/architectures/<name>.py``.  Such a module provides:

* ``program_fields``: ``model``-block key -> ``ModelConfig`` field;
* ``other_keys``: the ``model``-block keys that are no ``ModelConfig``
  field (a key in neither is refused);
* ``make_weights(model, key)``: the parameter tree, from a PRNG key;
* ``int8_matmul_calls(model, rows)``: ``(M, K, N)`` of every W8A8 GEMM
  of one decode step of ``rows`` rows;
* ``flops_per_token(model)``: 2 x the parameters a token multiplies by;
* ``attention_flops_per_position(model)``: attention flops of one token
  per cache position it attends, over every layer;
* ``verify_attention_cost(model, serving, T, keys)``: (flops, bytes) of
  one paged verify-attention call, windows of ``T``, ``keys`` live
  positions per row.

The layout: embeddings ``(V, D)``, per-layer ``attn`` q/k/v/o and ``ffn``
gate/up/down linears as ``{"w": (in, out)[, "b"]}``, RMSNorm
``{"scale"}``, and ``lm_head`` unless the embeddings are tied.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.roofline import flash_decode_paged_cost
from bench.weights import BF16, linear, norm

program_fields = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attn_bias",
}
# the harness checks ``hidden_act`` against the registry entry's activation
other_keys = frozenset({"hidden_act"})


def dims(model: dict) -> dict:
    """Derived sizes of a configuration's ``model`` block."""
    D = model["hidden_size"]
    H = model["num_attention_heads"]
    dh = model.get("head_dim", D // H)
    return {"D": D, "H": H, "Hkv": model["num_key_value_heads"], "dh": dh,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"]}


def make_weights(model: dict, key) -> dict:
    d = dims(model)
    D, F = d["D"], d["F"]
    qd, kvd = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    bias = bool(model.get("attention_bias", False))
    keys = jax.random.split(key, d["L"] + 3)
    layers = []
    for i in range(d["L"]):
        k = jax.random.split(keys[i], 9)
        layers.append({
            "attn_norm": norm(k[0], D),
            "attn": {"q": linear(k[1], D, qd, bias),
                     "k": linear(k[2], D, kvd, bias),
                     "v": linear(k[3], D, kvd, bias),
                     "o": linear(k[4], qd, D, False)},
            "ffn_norm": norm(k[5], D),
            "ffn": {"gate": linear(k[6], D, F, False),
                    "up": linear(k[7], D, F, False),
                    "down": linear(k[8], F, D, False)},
        })
    params = {
        "embed": {"w": (jax.random.normal(keys[-1], (d["V"], D),
                                          jnp.float32) * 0.02).astype(BF16)},
        "layers": layers,
        "final_norm": norm(keys[-2], D),
    }
    if not model.get("tie_word_embeddings", False):
        params["lm_head"] = linear(keys[-3], D, d["V"], False)
    return params


def block_linears(d: dict) -> list:
    """(K, N) of the seven linears of one block, in call order: q, k, v,
    o, gate, up, down."""
    D, F, qd, kvd = d["D"], d["F"], d["H"] * d["dh"], d["Hkv"] * d["dh"]
    return [(D, qd), (D, kvd), (D, kvd), (qd, D), (D, F), (D, F), (F, D)]


def int8_matmul_calls(model: dict, rows: int) -> list:
    """Every block's linears, then the LM head, which is W8A8 too unless
    it is tied to the bf16 embedding."""
    d = dims(model)
    calls = [(rows, K, N) for _ in range(d["L"]) for K, N in block_linears(d)]
    if not model.get("tie_word_embeddings", False):
        calls.append((rows, d["D"], d["V"]))
    return calls


def flops_per_token(model: dict) -> int:
    """Every block's linears and the LM head (the embedding lookup is
    free)."""
    d = dims(model)
    per_layer = sum(K * N for K, N in block_linears(d))
    return 2 * (d["L"] * per_layer + d["D"] * d["V"])


def attention_flops_per_position(model: dict) -> int:
    """``Q K^T`` and ``P V`` of every query head, every layer."""
    d = dims(model)
    return 4 * d["L"] * d["H"] * d["dh"]


def verify_attention_cost(model: dict, serving: dict, T: int, keys) -> tuple:
    """K and V of every KV head at each live position, with f32 scales
    where the cache is int8."""
    d = dims(model)
    int8 = serving["kv_cache_dtype"] == "int8"
    return flash_decode_paged_cost(T, d["H"], d["Hkv"], d["dh"], keys,
                                   1 if int8 else 2, int8)
