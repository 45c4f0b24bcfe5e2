"""Benchmark harness entry point — one function per paper table.

Prints ``name,us_per_call,derived`` CSV.  ``us_per_call`` is the measured
CPU wall time of one speculative serve step for that configuration (μs);
``derived`` is the table's headline metric (modeled TPU speedup, L, KL,
...).  Full rows land in benchmarks/results/*.json.

``python -m benchmarks.run [--quick]``
"""
from __future__ import annotations

import argparse
import sys
import time


def _bench_step_us() -> float:
    """One speculative serve step, CPU wall μs (jitted, post-warmup)."""
    import jax
    import jax.numpy as jnp

    from repro.core.config import SpecConfig
    from repro.core.spec_engine import init_state, make_serve_step
    from benchmarks.common import get_trained

    model, params, qparams = get_trained("qwen3-sub")
    scfg = SpecConfig(gamma=5, temperature=0.0)
    step = jax.jit(make_serve_step(model, scfg))
    state = init_state(model, 2, 256, jax.random.PRNGKey(0))
    prompts = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 6))
    P = prompts.shape[1]
    state["tokens"] = state["tokens"].at[:, :P].set(prompts)
    state["length"] = jnp.full((2,), P, jnp.int32)
    state["cache"] = model.prefill(qparams, state["cache"], prompts[:, :-1])
    state = step(qparams, state)              # warmup/compile
    jax.block_until_ready(state["tokens"])
    t0 = time.perf_counter()
    n = 20
    for _ in range(n):
        state = step(qparams, state)
    jax.block_until_ready(state["tokens"])
    return (time.perf_counter() - t0) / n * 1e6


def _acceptance_parity(quick: bool) -> str:
    """w8a8-vs-bf16 verify acceptance parity from *live* engine telemetry
    (``SpecEngine.telemetry`` accepted-length histograms), not offline
    tables — the paper's Table-1 invariant as a monitorable signal."""
    import jax.numpy as jnp

    from repro.core.config import SpecConfig
    from repro.data import task_prompts
    from repro.serving.engine import SpecEngine
    from benchmarks.common import get_trained

    model, params, _ = get_trained("qwen3-sub")
    prompts = jnp.asarray(
        task_prompts("gsm8k", 2, 48, model.cfg.vocab_size))
    new_tokens = 16 if quick else 64
    L = {}
    for verifier in ("bf16", "w8a8"):
        engine = SpecEngine(model, SpecConfig(gamma=5, temperature=0.0),
                            drafter="ngram", verifier=verifier)
        engine.generate(params, prompts, new_tokens)
        L[verifier] = engine.telemetry.mean_accept(f"ngram:{verifier}")
    return (f"bf16_L={L['bf16']:.2f};w8a8_L={L['w8a8']:.2f};"
            f"delta={L['w8a8'] - L['bf16']:+.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (CI-friendly)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (
        ablation_bits,
        ablation_kv,
        table1_speedup,
        table2_temperature,
        table3_sensitivity,
        table4_accuracy,
        table5_pruning,
        table6_tree,
        roofline_report,
    )

    step_us = _bench_step_us()
    lines = []

    t1 = table1_speedup.rows(quick=args.quick)
    q = [r for r in t1 if r["method"] == "quasar" and r["T"] == 0.0]
    n = [r for r in t1 if r["method"] == "ngram" and r["T"] == 0.0]
    avg = lambda rs, k: sum(r[k] for r in rs) / max(len(rs), 1)
    lines.append(("table1_quasar_T0", step_us,
                  f"speedup={avg(q, 'modeled_speedup'):.2f}x;L={avg(q, 'L'):.2f}"))
    lines.append(("table1_ngram_T0", step_us,
                  f"speedup={avg(n, 'modeled_speedup'):.2f}x;L={avg(n, 'L'):.2f}"))

    t2 = table2_temperature.rows(quick=args.quick)
    qT = [r for r in t2 if r["method"] == "quasar"]
    lines.append(("table2_temperature", step_us,
                  f"quasar_L_T0={qT[0]['L']:.2f};L_T1={qT[-1]['L']:.2f}"))

    t3 = table3_sensitivity.rows(quick=args.quick)
    best = max((r for r in t3 if r["method"] == "quasar"),
               key=lambda r: r["modeled_speedup"])
    lines.append(("table3_sensitivity", step_us,
                  f"best_gamma={best['gamma']};K={best['K']};speedup={best['modeled_speedup']:.2f}x"))

    t4 = table4_accuracy.rows(quick=args.quick)
    lines.append(("table4_accuracy", step_us,
                  f"kl={t4[0]['kl_fp_to_w8a8']:.2e};top1={t4[0]['top1_agreement']:.3f}"))

    t5 = table5_pruning.rows(quick=args.quick)
    qs = [r for r in t5 if r["method"] == "quasar"][0]
    p50 = [r for r in t5 if r["method"].startswith("pruned-5")]
    lines.append(("table5_pruning", step_us,
                  f"quasar={qs['modeled_speedup']:.2f}x;pruned50_L="
                  f"{p50[0]['L'] if p50 else 'n/a'}"))

    t6 = table6_tree.rows(quick=args.quick)
    t6w = [r for r in t6
           if r["verifier"] == "w8a8" and r["task"] == "ambiguous"]
    chain = [r for r in t6w if r["template"].startswith("chain")][0]
    widest = max(t6w, key=lambda r: r["leaves"])
    lines.append(("table6_tree", step_us,
                  f"chain_L={chain['L']:.2f};{widest['template']}_L="
                  f"{widest['L']:.2f};speedup={widest['modeled_speedup']:.2f}x"))

    ab = ablation_bits.rows(quick=args.quick)
    w4 = [r for r in ab if r["verifier"] == "w4a8"][0]
    lines.append(("ablation_bits", step_us,
                  f"w4a8_kl={w4['kl_vs_bf16']:.2e};L={w4['L']:.2f};"
                  f"speedup={w4['modeled_speedup']:.2f}x"))

    akv = ablation_kv.rows(quick=args.quick)
    m_int8 = [r for r in akv["modeled"]
              if r["kv_cache"] == "int8"][-1]          # longest context
    d_int8 = [r for r in akv["acceptance"] if r["kv_cache"] == "int8"][0]
    lines.append(("ablation_kv", step_us,
                  f"kv_bytes_ratio_{m_int8['context'] // 1024}k="
                  f"{m_int8['kv_bytes_vs_bf16']:.3f};"
                  f"L_delta={d_int8['L_delta_vs_bf16']:+.3f};"
                  f"speedup={m_int8['modeled_speedup']:.2f}x"))
    pg = akv["paged"]
    lines.append(("paged_kv", step_us,
                  f"footprint_vs_contig="
                  f"{pg['modeled_bf16']['paged_vs_contiguous']:.3f};"
                  f"measured_bytes_ratio="
                  f"{pg['measured_cpu']['paged_vs_contiguous_bytes']:.3f};"
                  f"lossless={pg['measured_cpu']['tokens_bit_identical']}"))
    sp = akv["shared_prefix"]
    lines.append(("prefix_sharing", step_us,
                  f"modeled_capacity="
                  f"{sp['modeled_bf16']['effective_capacity']:.2f}x;"
                  f"measured_capacity="
                  f"{sp['measured_cpu']['effective_capacity']:.2f}x;"
                  f"lossless={sp['measured_cpu']['tokens_bit_identical']}"))

    rr = roofline_report.rows(quick=args.quick)
    lines.append(("roofline", step_us,
                  f"dryrun_rows={len(rr['roofline'])};"
                  f"eq12_ratio={rr['eq11_12'][0]['ratio']:.2f}"))

    from benchmarks import serve_load
    sl = serve_load.rows(quick=args.quick)["headline"]
    lines.append(("serve_load", step_us,
                  f"fifo_hit={sl['fifo_hit_rate']:.3f};"
                  f"edf_shed_hit={sl['edf_shed_hit_rate']:.3f};"
                  f"edf_ttft_p99={sl['edf_shed_ttft_p99']:.2f}s"))

    lines.append(("acceptance_parity", step_us,
                  _acceptance_parity(args.quick)))

    print("name,us_per_call,derived")
    for name, us, derived in lines:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
