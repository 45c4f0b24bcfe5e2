"""Speculative serving for the modality archs (whisper enc-dec, VLM) and
the launch CLIs (subprocess smoke)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import SpecConfig
from repro.models import Model
from repro.serving.engine import SpecEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the launchers turn on the persistent compile cache; tests stay off it
ENV = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


@pytest.mark.parametrize("arch", ["whisper-small", "llama-3.2-vision-90b"])
def test_spec_serving_with_aux_embeds(arch):
    cfg = get_config(arch).reduced()
    m = Model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    n = cfg.num_image_tokens or cfg.num_audio_frames
    B = 2
    aux = jax.random.normal(jax.random.PRNGKey(7), (B, n, cfg.d_model), cfg.dtype)
    rng = np.random.default_rng(0)
    prompt = jnp.array(np.tile(rng.integers(0, cfg.vocab_size, 5), 4)
                       [None].repeat(B, 0).astype(np.int32))
    scfg = SpecConfig(gamma=3, temperature=0.0)
    rv = SpecEngine(m, scfg, mode="vanilla").generate(params, prompt, 8, aux_embeds=aux)
    rs = SpecEngine(m, scfg, mode="spec").generate(params, prompt, 8, aux_embeds=aux)
    P = prompt.shape[1]
    assert bool(jnp.all(rv.tokens[:, :P + 8] == rs.tokens[:, :P + 8]))
    assert rs.mean_accept_len >= 1.0


def test_serve_cli_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-135m",
         "--reduced", "--verifier", "w8a8", "--gamma", "3",
         "--batch", "2", "--prompt-len", "24", "--new-tokens", "8"],
        capture_output=True, text=True, timeout=600, env=ENV, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "mean acceptance length" in out.stdout


def test_serve_cli_fails_when_a_request_fails():
    """A contained request failure still ends the CLI with a non-zero
    status, so a broken serving path cannot pass for a working one."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-135m",
         "--reduced", "--serve", "--requests", "2", "--arrival-rate", "1000",
         "--batch", "2", "--prompt-len", "16", "--new-tokens", "4",
         "--fault-plan", "submit@0"],
        capture_output=True, text=True, timeout=600, env=ENV, cwd=ROOT)
    assert out.returncode != 0
    assert "1 request(s) failed" in out.stderr
    assert "served 1/2" in out.stdout


def test_train_cli_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "smollm-135m",
         "--reduced", "--steps", "3", "--batch", "2", "--seq-len", "32"],
        capture_output=True, text=True, timeout=600, env=ENV, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss" in out.stdout
