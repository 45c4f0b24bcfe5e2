"""The correctness check fails a broken timed path.

Each test drives a whole run of a tiny cell on the CPU through
``bench.run.run_once`` (the look for a chip skipped), with the decode
step under the window broken once set-up is done, and sees ``correct``
come out false: a step that returns its state unchanged, a step that
leaves half of the batch out, a step that alters the tokens it commits,
and the program's own lower-precision path (``w4a8``), the control.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness as H  # noqa: E402
from test_harness_cpu import TINY_OPEN, cpu_devices, write_cell  # noqa: E402,F401

# the tiny cell's limit, set from CPU readings on seeds 1-3: sound runs
# 0.0048-0.0123, the w4a8 control 0.158-0.293
TINY_LIMIT = 0.05
SEED = 2**31 + 11


def _broken(kind: str):
    import jax
    import jax.numpy as jnp

    def fault(step, params, state):
        out = step(params, state)
        if kind == "state_unchanged":
            return state
        B, S = state["tokens"].shape
        if kind == "half_batch":
            keep = jnp.arange(B) < B // 2
            out = dict(out)
            out["tokens"] = jnp.where(keep[:, None], out["tokens"],
                                      state["tokens"])
            out["length"] = jnp.where(keep, out["length"], state["length"])
            return out
        if kind == "token_altered":
            pos = jnp.arange(S)[None, :]
            new = (pos >= state["length"][:, None]) & \
                (pos < out["length"][:, None])
            out = dict(out)
            out["tokens"] = jnp.where(new, (out["tokens"] + 1) % 512,
                                      out["tokens"])
            return out
        raise ValueError(kind)

    def after_setup(loop):
        lane = next(iter(loop._lanes.values()))
        good = lane.step
        bad = jax.jit(lambda p, s: fault(good, p, s))
        jax.block_until_ready(bad(lane.params, lane.state))   # compile now
        lane.step = bad

    return after_setup


def _run(tmp_path, devices, **kw):
    from bench.run import run_once
    write_cell(tmp_path, "tiny-cell", TINY_OPEN, limit=TINY_LIMIT)
    cell = H.load_cell("tiny-cell", root=tmp_path)
    return run_once(cell, SEED, 1.0, False, devices, **kw)


def test_sound_run_is_correct(tmp_path, cpu_devices):
    res = _run(tmp_path, cpu_devices)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_broken_step_is_not_correct(tmp_path, cpu_devices, monkeypatch,
                                    kind):
    monkeypatch.setattr(H, "DRAIN_LIMIT_S", 2.0)
    res = _run(tmp_path, cpu_devices, after_setup=_broken(kind))
    assert res["correct"] is False, res["checks"]


def test_lower_precision_control_is_not_correct(tmp_path, cpu_devices):
    res = _run(tmp_path, cpu_devices, verifier="w4a8")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["served_gap_max"]["value"] > TINY_LIMIT
