"""A cell defined only by new files loads, and its traffic drives the
serving loop through the harness's own functions, at a tiny size on the
CPU; ``bench/run.py`` itself refuses to measure there.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness as H  # noqa: E402
from bench.traffic import generate  # noqa: E402

TINY_MODEL = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": True, "attention_bias": False,
    "hidden_act": "silu",
}
TINY_CONFIG = {
    "name": "tiny-dense", "source": "test", "registry": "smollm-135m",
    "reference": "dense", "model": TINY_MODEL,
    "reduced": ["hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "intermediate_size",
                "vocab_size"],
    "serving": {"verifier": "w8a8", "drafter": "ngram", "gamma": 3,
                "kv_cache_dtype": "bf16", "kv_block_size": 16,
                "temperature": 0.0},
}
TINY_OPEN = {
    "kind": "open", "arrivals": {"rate_per_s": 12.0, "cv": 2.0}, "slots": 4,
    "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
    "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "max_prompt_len": 40, "max_new_tokens": 16, "lengths": 4,
}
TINY_CLOSED = dict(TINY_OPEN, kind="closed", clients=6, rounds=6)
TINY_CLOSED.pop("arrivals")
# as many clients as slots: no request waits once the ramp is done
TINY_CLOSED_FULL = dict(TINY_CLOSED, clients=4, rounds=9)


def write_cell(root: Path, cell: str, mix: dict, limit: float = 1.0) -> None:
    """A benchmark of one cell, made of new files under ``root``."""
    if mix["kind"] == "open":
        judged = {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
                  "bound": 0.1, "source": "host_clock"}
    else:
        judged = {"name": "tokens_per_s", "unit": "tokens/s",
                  "better": "higher", "bound": 0.1, "source": "host_clock"}
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "limits").mkdir(parents=True)
    (root / "bench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(
        {"served_gap_max": {"limit": limit}}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-dense", "source": "test",
                     "file": "bench/configs/tiny-dense.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": cell, "config": "tiny-dense",
                       "traffic": "tiny_mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            judged,
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "queue_wait_p50_ms", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "front end",
             "moves": judged["name"]}],
    }))


@pytest.fixture(scope="module")
def cpu_devices():
    import jax
    devs = jax.devices()
    if devs[0].platform != "cpu":
        pytest.skip("CPU-only test")
    return devs[:1]


@pytest.mark.parametrize("mix", [TINY_OPEN, TINY_CLOSED, TINY_CLOSED_FULL],
                         ids=["open", "closed", "closed_full"])
def test_new_cell_loads_and_drives_the_loop(tmp_path, cpu_devices, mix):
    from bench.run import run_once
    write_cell(tmp_path, "tiny-cell", mix)
    cell = H.load_cell("tiny-cell", root=tmp_path)
    assert cell.config["name"] == "tiny-dense"
    assert [m["name"] for m in cell.per_layer] == ["queue_wait_p50_ms"]
    res = run_once(cell, seed=2**31 + 7, seconds=1.0, trace=False,
                   devices=cpu_devices)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["checked_tokens"]["value"] > 0
    judged = "ttft_p95_ms" if mix["kind"] == "open" else "tokens_per_s"
    assert set(res["metrics"]) == {judged, "setup_s"}
    assert res["metrics"][judged]["value"] > 0


def test_same_seed_same_traffic_and_every_seed_same_work():
    a = generate(TINY_OPEN, 5, 512, 4.0, warmup_new_tokens=1)
    b = generate(TINY_OPEN, 5, 512, 4.0, warmup_new_tokens=1)
    c = generate(TINY_OPEN, 2**40 + 5, 512, 4.0,
                 warmup_new_tokens=1)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               for x, y in zip(a.requests, b.requests))
    assert sorted(r.prompt.size for r in a.requests) == \
        sorted(r.prompt.size for r in c.requests)
    assert sorted(r.max_new_tokens for r in a.requests) == \
        sorted(r.max_new_tokens for r in c.requests)
    assert [r.prompt.tolist() for r in a.requests] != \
        [r.prompt.tolist() for r in c.requests]
    dues = [r.due_s for r in a.requests]
    assert dues[0] == 0.0 and max(dues) < 4.0 and len(dues) == 48


def test_run_py_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "smollm-chat-closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
