"""A toy sparse-expert decoder, as a later configuration would add one:
attention as in the dense block, then a router over ``n_routed_experts``
SwiGLU experts of width ``moe_intermediate_size``, ``num_experts_per_tok``
of them per token, their weights stacked ``(E, in, out)`` for a grouped
GEMM kernel (``expert_gemm``)."""
import jax
import jax.numpy as jnp

from bench.roofline import flash_decode_paged_cost
from bench.weights import BF16, linear, norm

program_fields = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff", "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "sliding_window": "sliding_window", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
other_keys = frozenset({"hidden_act", "scoring_func"})


def _sizes(m):
    return (m["hidden_size"], m["num_attention_heads"] * m["head_dim"],
            m["num_key_value_heads"] * m["head_dim"],
            m["moe_intermediate_size"], m["n_routed_experts"])


def _experts(key, E, din, dout):
    w = jax.random.normal(key, (E, din, dout), jnp.float32) * din ** -0.5
    return {"w": w.astype(BF16)}


def make_weights(model, key):
    D, qd, kvd, F, E = _sizes(model)
    keys = jax.random.split(key, model["num_hidden_layers"] + 3)
    layers = []
    for lk in keys[:-3]:
        k = jax.random.split(lk, 10)
        layers.append({
            "attn_norm": norm(k[0], D),
            "attn": {"q": linear(k[1], D, qd, False),
                     "k": linear(k[2], D, kvd, False),
                     "v": linear(k[3], D, kvd, False),
                     "o": linear(k[4], qd, D, False)},
            "moe_norm": norm(k[5], D),
            "moe": {"router": linear(k[6], D, E, False),
                    "experts": {"gate": _experts(k[7], E, D, F),
                                "up": _experts(k[8], E, D, F),
                                "down": _experts(k[9], E, F, D)}},
        })
    V = model["vocab_size"]
    return {"embed": {"w": (jax.random.normal(keys[-1], (V, D), jnp.float32)
                            * 0.02).astype(BF16)},
            "layers": layers, "final_norm": norm(keys[-2], D),
            "lm_head": linear(keys[-3], D, V, False)}


def int8_matmul_calls(model, rows):
    """Attention's four linears per layer, then the head."""
    D, qd, kvd, _, _ = _sizes(model)
    per_layer = [(D, qd), (D, kvd), (D, kvd), (qd, D)]
    return ([(rows, K, N) for _ in range(model["num_hidden_layers"])
             for K, N in per_layer] + [(rows, D, model["vocab_size"])])


def expert_gemm_calls(model, rows):
    """(experts, routed rows, K, N) of the grouped gate, up and down GEMMs
    of every layer."""
    D, _, _, F, E = _sizes(model)
    M = rows * model["num_experts_per_tok"]
    return [c for _ in range(model["num_hidden_layers"])
            for c in ((E, M, D, F), (E, M, D, F), (E, M, F, D))]


def flops_per_token(model):
    D, qd, kvd, F, E = _sizes(model)
    k = model["num_experts_per_tok"]
    per_layer = 2 * D * qd + 2 * D * kvd + D * E + 3 * k * D * F
    return 2 * (model["num_hidden_layers"] * per_layer
                + D * model["vocab_size"])


def attention_flops_per_position(model):
    return (4 * model["num_hidden_layers"] * model["num_attention_heads"]
            * model["head_dim"])


def verify_attention_cost(model, serving, T, keys):
    int8 = serving["kv_cache_dtype"] == "int8"
    return flash_decode_paged_cost(
        T, model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"], keys, 1 if int8 else 2, int8)
