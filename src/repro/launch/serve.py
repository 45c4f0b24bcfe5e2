"""Serving launcher: one process on one device.

On a TPU chip the Pallas kernels run compiled (``repro.kernels.ops``);
``chip_smoke.py`` at the checkout root is the full-width check of this
path on one chip.  No code path here uses more than one device.  On a
CPU (``JAX_PLATFORMS=cpu``) pass ``--reduced`` to run the identical code
path at smoke scale, with the kernels' jnp twins.

Drafting and verification are registry plugins: ``--drafter`` /
``--verifier`` name any registered implementation, and the engine applies
the verifier's offline weight preparation itself — ``--verifier w8a8``
alone serves quantized verification from a BF16 checkpoint.

  python -m repro.launch.serve --arch smollm-135m --reduced \
      --verifier w8a8 --gamma 5 --batch 4 --new-tokens 32
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.config import SpecConfig
from repro.core.protocols import available_drafters, available_verifiers
from repro.data import task_prompts
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving.engine import LEGACY_MODES, SpecEngine
from repro.train.checkpoint import load_checkpoint


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--verifier", default="w8a8",
                    choices=list(available_verifiers()))
    ap.add_argument("--kv-cache", default="bf16", choices=["bf16", "int8"])
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="serving-path cache layout; 'paged' routes the "
                         "batch through the continuous-batching scheduler "
                         "with block-granular KV allocation "
                         "(core/paged_cache.py); solo generate stays "
                         "contiguous")
    ap.add_argument("--kv-block-size", type=int, default=128,
                    help="tokens per paged KV block (--kv-layout paged)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="--kv-layout paged: disable the prefix cache "
                         "(refcounted block sharing of common prompt "
                         "prefixes + copy-on-write boundary forking)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="--kv-layout paged: never evict running slots "
                         "to the host swap pool; denied admissions wait "
                         "for capacity instead")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="decode/verify attention path: auto = Pallas "
                         "flash-decode kernel on TPU (interpret under "
                         "REPRO_USE_PALLAS=1) else jnp; pallas/jnp force "
                         "one side")
    ap.add_argument("--drafter", default=None,
                    choices=list(available_drafters()))
    ap.add_argument("--mode", default=None, choices=list(LEGACY_MODES),
                    help="deprecated alias: spec|vanilla|pruned -> --drafter")
    ap.add_argument("--gamma", type=int, default=None,
                    help="draft length (default 5); with --tree-branches "
                         "the template fixes the draft length instead")
    ap.add_argument("--tree-branches", default=None,
                    help="comma-separated per-depth branch factors for "
                         "tree drafters, e.g. '3,2,1,1' (--drafter "
                         "ngram-tree); default: the (1,)*gamma chain")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--task", default="gsm8k")
    ap.add_argument("--ckpt", default=None, help="checkpoint (.npz) to serve")
    ap.add_argument("--serve", action="store_true",
                    help="async front-end demo: run a StreamingServer on "
                         "the wall clock, submit a Poisson arrival stream, "
                         "stream tokens per request, print the metrics "
                         "summary (repro.serving.server)")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests in the --serve arrival stream")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="--serve Poisson arrival rate (requests/s)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="--serve per-request SLO deadline in seconds "
                         "(default: no deadline)")
    ap.add_argument("--admission", default="edf", choices=["fifo", "edf"],
                    help="--serve admission policy within priority class")
    ap.add_argument("--no-shed", action="store_true",
                    help="--serve: keep past-deadline queued work instead "
                         "of shedding it")
    ap.add_argument("--request-timeout", type=float, default=None,
                    metavar="S",
                    help="--serve: fail any request older end-to-end than "
                         "S seconds (RequestTimeout) — turns a hung lane "
                         "into per-request failures, never blocked callers")
    ap.add_argument("--collapse-window", type=int, default=0,
                    metavar="N",
                    help="--serve: acceptance-collapse detector window (N "
                         "decode steps; 0 = off).  A quantized-verifier "
                         "lane whose mean acceptance sits below "
                         "--collapse-threshold for a full window is "
                         "re-prepared (re-quantized) — docs/robustness.md")
    ap.add_argument("--collapse-threshold", type=float, default=0.05,
                    metavar="T",
                    help="--serve: mean accepted tokens per row-step below "
                         "which the collapse detector trips")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="--serve: inject faults from a seeded FaultPlan "
                         "spec (seam@i / seam~p, comma-separated, e.g. "
                         "'step@3,alloc~0.05') to rehearse containment — "
                         "see repro.serving.faults")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --fault-plan")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event / Perfetto JSON of "
                         "the run (request lifecycle, scheduler ticks, "
                         "decode/prefill/swap spans); open at "
                         "https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics summary (latency, acceptance, "
                         "kv_cache sections) as JSON")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write the metrics in Prometheus text exposition "
                         "format (ServerMetrics.expose_text)")
    args = ap.parse_args()
    enable_compile_cache()

    import dataclasses

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.kv_cache != "bf16":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=args.kv_cache)
    if args.attn_impl != "auto":
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    model = Model(cfg)

    if args.ckpt:
        params = load_checkpoint(args.ckpt)
        params = params.get("params", params)
    else:
        print("no --ckpt: serving random-init weights (demo)")
        params = model.init_params(jax.random.PRNGKey(0))

    branches = (tuple(int(b) for b in args.tree_branches.split(","))
                if args.tree_branches else None)
    # --tree-branches implies the tree drafter; reject combinations that
    # would silently ignore the template
    default_drafter = "ngram-tree" if branches is not None else "ngram"
    drafter = args.drafter or LEGACY_MODES.get(args.mode) or default_drafter
    if branches is not None:
        if args.gamma is not None:
            ap.error("--gamma conflicts with --tree-branches: the template "
                     "fixes the draft length (nodes - 1)")
        if drafter != "ngram-tree":
            ap.error(f"--tree-branches is only read by tree drafters; "
                     f"drafter {drafter!r} would silently ignore it")
    scfg = SpecConfig(gamma=args.gamma if args.gamma is not None else 5,
                      temperature=args.temperature,
                      k_min=1, k_max=4, drafter=drafter,
                      verifier=args.verifier, tree_branches=branches,
                      kv_layout=args.kv_layout,
                      kv_block_size=args.kv_block_size,
                      kv_prefix_sharing=not args.no_prefix_sharing,
                      kv_preempt=not args.no_preempt)
    # the engine's verifier quantizes internally when scfg.verifier demands it
    engine = SpecEngine(model, scfg)
    prompts = jnp.asarray(task_prompts(
        args.task, args.batch, args.prompt_len, cfg.vocab_size))
    from repro.kernels.ops import attn_backend
    attn_path = cfg.attn_impl if cfg.attn_impl != "auto" else attn_backend()
    platform = jax.devices()[0].platform
    print(f"arch={cfg.name} verifier={engine.verifier.name} "
          f"drafter={engine.drafter.name} kv_cache={cfg.kv_cache_dtype} "
          f"kv_layout={args.kv_layout} attn={attn_path} "
          f"device={platform}:{jax.devices()[0].device_kind}")

    import json

    from repro.serving.trace import Tracer
    tracer = Tracer() if args.trace_out else None

    def dump_observability(metrics=None):
        """Write --trace-out / --metrics-out / --prom-out artifacts."""
        if tracer is not None:
            tracer.save(args.trace_out)
            print(f"trace: {args.trace_out} "
                  f"({len(tracer.events)} events)")
        if args.metrics_out:
            if metrics is not None:
                payload = metrics.summary()
            else:  # batch path: engine-level telemetry only
                payload = {"acceptance": engine.telemetry.summary()}
            with open(args.metrics_out, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"metrics: {args.metrics_out}")
        if args.prom_out:
            if metrics is None:
                print("--prom-out needs --serve (ServerMetrics); skipped")
            else:
                with open(args.prom_out, "w") as f:
                    f.write(metrics.expose_text())
                print(f"prometheus: {args.prom_out}")

    if args.serve:
        import numpy as np

        from repro.serving import FaultPlan, GenerationRequest, \
            ServerConfig, StreamingServer
        cfg_srv = ServerConfig(
            batch_slots=args.batch,
            max_prompt_len=args.prompt_len,
            max_new_tokens=args.new_tokens,
            admission=args.admission,
            shed_late=not args.no_shed,
            request_timeout_s=args.request_timeout,
            collapse_window=args.collapse_window,
            collapse_threshold=args.collapse_threshold,
        )
        faults = (FaultPlan.parse(args.fault_plan, seed=args.fault_seed)
                  if args.fault_plan else None)
        rng = np.random.default_rng(0)
        gaps = rng.exponential(1.0 / max(args.arrival_rate, 1e-9),
                               size=args.requests)
        pool = np.asarray(prompts)
        t0 = time.perf_counter()
        with StreamingServer(engine, params, cfg_srv, tracer=tracer,
                             faults=faults) as srv:
            handles = []
            for i in range(args.requests):
                time.sleep(gaps[i])
                h = srv.submit(GenerationRequest(
                    pool[i % len(pool)], args.new_tokens, seed=i,
                    deadline_s=args.deadline))
                handles.append(h)
            for h in handles:
                toks = list(h.tokens())       # blocking per-token stream
                try:
                    res = h.result(timeout=60.0)
                except Exception as exc:      # failed request: contained
                    res = None
                    print(f"req {h.rid}: failed "
                          f"({type(exc).__name__}: {exc})")
                else:
                    print(f"req {h.rid}: {h.status}, {len(toks)} chunks, "
                          f"{res.new_tokens if res else 0} tokens")
            summary = srv.loop.metrics.summary()
        wall = time.perf_counter() - t0
        srv.loop.metrics.check_conservation()
        dump_observability(metrics=srv.loop.metrics)
        c = summary["counters"]
        lat = summary["latency"]
        print(f"served {c['completed']}/{c['submitted']} "
              f"(shed {c['shed']}, failed {c['failed']}) "
              f"in {wall:.2f}s wall")
        rb = {k: v for k, v in summary["robustness"].items() if v}
        if rb:
            print("robustness: "
                  + ", ".join(f"{k}={v}" for k, v in sorted(rb.items())))
        ttft, itl = lat["ttft_s"], lat["itl_s"]
        if ttft.get("n"):
            print(f"ttft p50={ttft['p50']:.3f}s p99={ttft['p99']:.3f}s  "
                  f"itl p50={itl.get('p50', float('nan')):.4f}s")
        if summary["deadlines"]["with_deadline"]:
            print(f"deadline hit-rate: "
                  f"{summary['deadlines']['hit_rate']:.3f}")
        if c["failed"] or rb:
            sys.exit(f"serve: {c['failed']} request(s) failed, "
                     f"guardrails fired: {sorted(rb) or 'none'}")
        return
    if args.kv_layout == "paged":
        # paged is a serving-path layout: route the batch through the
        # continuous-batching scheduler as per-request generations
        import numpy as np

        from repro.serving import GenerationRequest
        reqs = [GenerationRequest(np.asarray(p), args.new_tokens, seed=i)
                for i, p in enumerate(np.asarray(prompts))]
        t0 = time.perf_counter()
        out = engine.generate_requests(params, reqs, tracer=tracer)
        wall = time.perf_counter() - t0
        failed = [i for i, r in enumerate(out) if r is None]
        if failed:
            sys.exit(f"serve: requests {failed} failed")
        new_tokens = sum(r.new_tokens for r in out)
        L = sum(r.accept_len for r in out) / len(out)
        steps = max(r.steps for r in out)
        print(f"generated {new_tokens} tokens in {wall:.2f}s "
              f"({new_tokens / max(wall, 1e-9):.1f} tok/s {platform}, "
              "compile included)")
        print(f"verify steps={steps}  mean acceptance length L={L:.3f}")
        dump_observability()
        return
    r = engine.generate(params, prompts, args.new_tokens)
    print(f"generated {r.new_tokens} tokens in {r.wall_s:.2f}s "
          f"({r.tokens_per_s:.1f} tok/s {platform}, compile included)")
    print(f"verify steps={r.steps}  mean acceptance length L={r.mean_accept_len:.3f}")
    dump_observability()


if __name__ == "__main__":
    main()
