"""Rehearsal of ``chip_smoke.py`` on the CPU: its phases at a reduced
width, with the Pallas kernels in interpret mode, and the script's refusal
to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    """Phase B at a reduced width with the kernels in interpret mode:
    (cfg, params, requests, warm-up requests, lane results)."""
    import jax

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_FORCE_PALLAS", True)
        cfg = get_config(smoke.ARCH).reduced()
        params = Model(cfg).init_params(jax.random.PRNGKey(smoke.SEED))
        requests = smoke.make_traffic(cfg.vocab_size, n=3, prompt_len=(8, 16),
                                      new_tokens=(6, 10))
        warmup = smoke.make_traffic(cfg.vocab_size, n=2, prompt_len=(8, 8),
                                    new_tokens=(3, 3), seed=smoke.SEED + 1)
        lanes = smoke.phase_serving(cfg, params, requests, warmup)
        yield cfg, params, requests, warmup, lanes


def test_kernel_parity_phase(smoke):
    cfg = get_config(smoke.ARCH).reduced()
    errs = smoke.phase_kernels(cfg, interpret=True)
    assert len(errs) == 8 and max(errs.values()) <= smoke.PARITY_RTOL


def test_serving_and_lossless_phases(smoke, served):
    cfg, params, requests, warmup, lanes = served
    assert set(lanes) == {name for name, *_ in smoke.LANES}
    for r in lanes.values():
        assert r["traces"] == 2                 # T = 0 and T = HOT_T
        assert r["new_tokens"] == sum(q.max_new_tokens for q in requests)
    diffs = smoke.phase_lossless(cfg, params, requests, warmup, lanes)
    assert diffs == {name: [] for name, *_ in smoke.LANES}


@pytest.mark.parametrize("lane,error", [
    ("chain", "differ from vanilla"),
    ("tree+int8", "beyond a tie"),
])
def test_lossless_phase_catches_a_wrong_token(smoke, served, lane, error):
    """A greedy token that is not the model's choice fails phase C: at
    once in the chain lane, through the reference in a tree lane."""
    cfg, params, requests, warmup, lanes = served
    tokens = [t.copy() for t in lanes[lane]["tokens"]]
    tokens[0][2] = (tokens[0][2] + 1) % cfg.vocab_size
    wrong = {**lanes, lane: {**lanes[lane], "tokens": tokens}}
    with pytest.raises(AssertionError, match=error):
        smoke.phase_lossless(cfg, params, requests, warmup, wrong)


def test_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
