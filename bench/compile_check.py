"""Compile each cell's decode step and admission prefill for a described
TPU v5e chip, with no chip attached, and report ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [--cells a,b] [--perf PERF.md]

Nothing runs and no array is made: weights, state and cache are shapes
(``jax.eval_shape``), sized as the serving lane sizes them for the
cell's mix.  The program decides its kernels by the backend it finds, so
this script tells it that it is on a TPU.  ``--perf`` writes the table
between the ``compile_check`` markers of that file.

What it shows: whether the TPU compiler accepts the step at the cell's
widths, and the bytes of one program (arguments, outputs, temporaries).
The process holds more than one program: the raw bf16 weights beside the
W8A8 ones, and the eager admission prefill's own buffers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

MARK_BEGIN = "<!-- compile_check:begin -->"
MARK_END = "<!-- compile_check:end -->"
GIB = 2.0 ** 30


def lane_sizes(mix: dict, serving: dict) -> dict:
    """Buffer, pool and table sizes of a serving lane for ``mix``
    (as ``serving/server.py`` sizes a paged lane with prefix sharing)."""
    from repro.core.paged_cache import blocks_for_tokens, request_demand_tokens
    g, bs = serving["gamma"], serving["kv_block_size"]
    P, N, slots = mix["max_prompt_len"], mix["max_new_tokens"], mix["slots"]
    buf = P + N + g + 2
    demand = blocks_for_tokens(request_demand_tokens(P, N, g), bs)
    return {"slots": slots, "buf": buf, "pmax": P,
            "num_blocks": 1 + slots * (demand + 1),
            "max_blocks": blocks_for_tokens(buf, bs), "block": bs}


def compile_cell(cell, device) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from bench.harness import architecture, program_config
    from repro.core.config import SpecConfig
    from repro.core.paged_cache import init_paged_cache
    from repro.core.spec_engine import init_state
    from repro.models import Model
    from repro.serving.engine import SpecEngine

    s = cell.config["serving"]
    model = Model(program_config(cell.config))
    engine = SpecEngine(model, SpecConfig(
        gamma=s["gamma"], temperature=s["temperature"], drafter=s["drafter"],
        verifier=s["verifier"], kv_layout="paged",
        kv_block_size=s["kv_block_size"]))
    z = lane_sizes(cell.mix, s)
    build = architecture(cell.config).make_weights
    raw = jax.eval_shape(lambda: build(cell.config["model"],
                                       jax.random.PRNGKey(0)))
    prepared = jax.eval_shape(engine.prepare_params, raw)

    def state():
        cache = init_paged_cache(model.cfg, z["slots"], z["max_blocks"],
                                 z["num_blocks"], z["block"])
        return init_state(model, z["slots"], z["buf"],
                          jnp.zeros((z["slots"], 2), jnp.uint32),
                          target=jnp.zeros((z["slots"],), jnp.int32),
                          cache=cache)

    sh = SingleDeviceSharding(device)

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)

    out = {"sizes": z}
    t0 = time.perf_counter()
    step = engine._step.lower(place(prepared), place(jax.eval_shape(state)))
    compiled = step.compile()
    out["step"] = _mem(compiled, time.perf_counter() - t0)
    out["step"]["kernels"] = compiled.as_text().count("tpu_custom_call")

    row = jax.eval_shape(lambda: model.init_cache(1, z["buf"]))
    toks = jax.ShapeDtypeStruct((1, z["pmax"] - 1), jnp.int32, sharding=sh)
    t0 = time.perf_counter()
    pre = jax.jit(model.prefill).lower(place(prepared), place(row), toks)
    out["prefill"] = _mem(pre.compile(), time.perf_counter() - t0)
    out["weights_bytes"] = {
        "prepared": _nbytes(prepared), "raw_bf16": _nbytes(raw)}
    return out


def _nbytes(tree) -> int:
    import jax
    return int(sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree)))


def _mem(compiled, seconds: float) -> dict:
    m = compiled.memory_analysis()
    return {"argument": int(m.argument_size_in_bytes),
            "output": int(m.output_size_in_bytes),
            "temp": int(m.temp_size_in_bytes),
            "alias": int(m.alias_size_in_bytes),
            "compile_s": round(seconds, 1)}


def table(results: dict) -> str:
    lines = ["| Cell | slots, pool blocks | W8A8 + raw bf16 weights (GiB) | "
             "step args / out / temp (GiB) | prefill temp (GiB) | "
             "kernels in step |",
             "| --- | --- | --- | --- | --- | --- |"]
    for name, r in results.items():
        if "error" in r:
            lines.append(f"| `{name}` | refused: {r['error'][:120]} | | | | |")
            continue
        w, st, pf, z = r["weights_bytes"], r["step"], r["prefill"], r["sizes"]
        lines.append(
            f"| `{name}` | {z['slots']}, {z['num_blocks']} | "
            f"{w['prepared'] / GIB:.2f} + {w['raw_bf16'] / GIB:.2f} | "
            f"{st['argument'] / GIB:.2f} / {st['output'] / GIB:.2f} / "
            f"{st['temp'] / GIB:.2f} | {pf['temp'] / GIB:.2f} | "
            f"{st['kernels']} |")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default=None,
                    help="comma-separated cells (default: every cell)")
    ap.add_argument("--perf", default=None,
                    help="write the table between the markers of this file")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from bench.harness import load_cell
    import repro.kernels.ops as ops
    ops._on_tpu = lambda: True          # compile the TPU kernels
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = (args.cells.split(",") if args.cells else
             [w["name"] for w in json.loads(
                 (ROOT / "BENCHMARK.json").read_text())["workloads"]])
    results = {}
    for name in names:
        try:
            results[name] = compile_cell(load_cell(name), topo.devices[0])
        except Exception as exc:  # noqa: BLE001 — report and go on
            results[name] = {"error": f"{type(exc).__name__}: {exc}"}
        print(name, json.dumps(results[name]), flush=True)
    text = table(results)
    print(text)
    if args.perf:
        path = Path(args.perf)
        doc = path.read_text()
        a, b = doc.index(MARK_BEGIN) + len(MARK_BEGIN), doc.index(MARK_END)
        path.write_text(doc[:a] + "\n" + text + "\n" + doc[b:])
    return 0 if all("error" not in r for r in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
