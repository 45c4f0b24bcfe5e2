"""Shared benchmark harness.

CPU-scale methodology (nothing here is measured on a chip):

* **Acceptance lengths (L)** are *measured* — they are hardware-independent
  (they depend only on the token streams and the verifier's logits).
* **Wall-clock** is measured on CPU and reported for the spec-vs-vanilla
  structure (fewer verifier passes); it can NOT show the W8A8 bandwidth
  win (CPU has no int8 tensor cores — the int8 GEMM simulation is the
  same speed or slower than f32).
* **Modeled TPU speed** uses the paper's own latency model (Eq. 11-13)
  with TPU v5e constants and the measured L: per speculative step,
  T_verify = max(weight+cache bytes / HBM_bw, flops / peak), drafting cost
  per its kind.  This is the column compared against the paper's tables.

Two "target models" stand in for the paper's Qwen3-8B / OpenPangu-7B at
CPU-tractable scale (trained briefly on the synthetic Markov corpus so
logits have real structure); the modeled-speed column uses the *full*
paper-scale config (quasar-paper-7b) for the Eq. 11-13 byte counts.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.config import QuantConfig, SpecConfig
from repro.data import lm_batches, task_prompts
from repro.models import Model
from repro.quant import quantize_params
from repro.serving.engine import SpecEngine
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
TASKS = ["mtbench", "humaneval", "gsm8k", "alpaca", "cnndm"]

# TPU v5e
HBM_BW = 819e9
PEAK_BF16 = 197e12
PEAK_INT8 = 394e12


# ---------------------------------------------------------------------------
# Small trained stand-in models (cached on disk)
# ---------------------------------------------------------------------------

_MODEL_DEFS = {
    # reduced smollm family ≈ "Qwen3" stand-in
    "qwen3-sub": ("smollm-135m", 0),
    # slightly different seed/init ≈ "OpenPangu" stand-in
    "openpangu-sub": ("smollm-135m", 7),
}


def get_trained(name: str, steps: int = 400):
    arch, seed = _MODEL_DEFS[name]
    cfg = get_config(arch).reduced()
    m = Model(cfg)
    path = os.path.join(RESULTS_DIR, f"cache_{name}.npz")
    if os.path.exists(path):
        params = load_checkpoint(path)
    else:
        tr = Trainer(m, AdamWConfig(lr=1.5e-3, warmup_steps=20, total_steps=steps))
        params, opt = tr.init(jax.random.PRNGKey(seed))
        # fairly deterministic Markov corpus: a well-trained model then puts
        # high probability on in-pattern continuations, which is what makes
        # T=1 acceptance behave like the paper's real-LLM setting
        params, _, _ = tr.fit(params, opt,
                              lm_batches(8, 96, cfg.vocab_size, seed=seed,
                                         markov_alpha=0.97),
                              steps=steps, log_every=steps, log_fn=None)
        os.makedirs(RESULTS_DIR, exist_ok=True)
        save_checkpoint(path, params)
    # calibrate + quantize
    collect = {}
    batch = next(lm_batches(4, 96, cfg.vocab_size, seed=seed + 1,
                            markov_alpha=0.97))
    m.forward(params, jnp.asarray(batch["tokens"]), collect=collect)
    qparams = quantize_params(params, collect, QuantConfig())
    return m, params, qparams


# ---------------------------------------------------------------------------
# Eq. 11-13 analytic latency model (paper §3.4), paper-scale config
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LatencyModel:
    """Per-speculative-step verify/draft latency for the paper-scale model."""
    cfg: object = None
    batch: int = 1
    context: int = 1024

    def __post_init__(self):
        if self.cfg is None:
            self.cfg = get_config("quasar-paper-7b")

    def _weight_bytes(self, bits: int) -> float:
        n = self.cfg.active_param_count()
        return n * bits / 8 + (n / self.cfg.d_model) * 4.0  # + per-channel scales

    def _kv_bytes(self, kv_bits: int = 16) -> float:
        from repro.launch.roofline import kv_cache_read_bytes
        return kv_cache_read_bytes(
            self.cfg, self.batch, self.context,
            "int8" if kv_bits <= 8 else "bf16")

    def t_verify(self, gamma: int, bits: int, kv_bits: int = 16) -> float:
        """Eq. 11/12: memory term + compute term for a (γ+1)-token window.
        ``kv_bits=8`` models the int8 KV cache (halved K/V stream + f32
        scale rows, matching ``roofline.kv_cache_read_bytes``)."""
        c = self.cfg
        tokens = self.batch * (gamma + 1)
        mem = (self._weight_bytes(bits) + self._kv_bytes(kv_bits)) / HBM_BW
        peak = PEAK_INT8 if bits <= 8 else PEAK_BF16
        comp = 2.0 * c.active_param_count() * tokens / peak
        return max(mem, comp) + 20e-6  # fixed launch overhead

    def t_vanilla_token(self, bits: int = 16) -> float:
        return self.t_verify(0, bits)

    def t_draft_ngram(self) -> float:
        # on-device token-buffer scan: tiny vs a forward pass
        return (self.batch * self.context * 4 * 4) / HBM_BW + 10e-6

    def t_draft_pruned(self, gamma: int, retention: float, bits: int = 16) -> float:
        # γ sequential single-token decodes of the layer-dropped model
        return gamma * retention * self.t_vanilla_token(bits)

    def speedup(self, L: float, gamma: int, *, verifier_bits: int,
                drafter: str = "ngram", retention: float = 1.0,
                kv_bits: int = 16) -> float:
        """Eq. 13 vs the BF16 autoregressive baseline (bf16 weights + KV)."""
        t_v = self.t_verify(gamma, verifier_bits, kv_bits)
        t_d = (self.t_draft_ngram() if drafter == "ngram"
               else self.t_draft_pruned(gamma, retention))
        per_step = t_d + t_v
        return (L * self.t_vanilla_token(16)) / per_step


# ---------------------------------------------------------------------------
# Engine-run helper: measured L + CPU wall
# ---------------------------------------------------------------------------

def run_engine(model, params, *, mode=None, drafter=None, verifier=None,
               scfg, task="gsm8k", batch=2, prompt_len=48, new_tokens=24,
               seed=0, draft_params=None):
    """Measure one engine config.  ``drafter``/``verifier`` name registry
    plugins (``repro.core.protocols``); ``mode`` is the deprecated alias
    ("spec"|"vanilla"|"pruned") used by the seed-era tables.  Benchmarks
    pass pre-prepared params, so the default verifier is passthrough BF16
    — name ``verifier="w8a8"`` to let the engine quantize internally."""
    prompts = jnp.asarray(
        task_prompts(task, batch, prompt_len, model.cfg.vocab_size, seed=seed))
    if mode is not None:
        eng = SpecEngine(model, scfg, mode=mode,
                         drafter=drafter, verifier=verifier)
    else:
        eng = SpecEngine(model, scfg, drafter=drafter or scfg.drafter,
                         verifier=verifier or "bf16")
    # warm-up for compile, then measure
    r = eng.generate(params, prompts, new_tokens, key=jax.random.PRNGKey(seed),
                     draft_params=draft_params)
    t0 = time.perf_counter()
    r = eng.generate(params, prompts, new_tokens, key=jax.random.PRNGKey(seed + 1),
                     draft_params=draft_params)
    wall = time.perf_counter() - t0
    return {
        "L": r.mean_accept_len,
        "steps": r.steps,
        "cpu_tok_s": r.new_tokens / wall,
        "new_tokens": r.new_tokens,
    }


def save_json(name: str, obj) -> str:
    import json
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path
