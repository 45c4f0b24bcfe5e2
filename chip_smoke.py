"""Bring-up check of the speculative serving path on one TPU chip.

    python3 chip_smoke.py

One process, one chip, no options.  The model is smollm-135m at its
published widths (30 layers, d_model 576, 9/3 heads of 64, d_ff 1536,
vocab 49152) with seeded random weights; nothing is downloaded.

* Phase A, kernel parity: ``smooth_quant`` + ``int8_matmul`` against
  ``ref.w8a8_matmul_ref`` at the verify M and a prefill M, and
  ``flash_decode_paged`` (plain, int8, tree, tree+int8) against the jnp
  ``attend_paged``, each within ``PARITY_RTOL``.
* Phase B, serving: two ``ServingLoop`` lanes on the real clock, paged
  KV and the W8A8 verifier — the ``ngram`` chain drafter over a bf16
  cache, and ``ngram-tree`` with a root-heavy template over an int8
  cache.  Every request completes, no guardrail fires, the decode step
  compiles once per lane and temperature, and the compiled step holds
  the Mosaic kernels (``tpu_custom_call``).
* Phase C, losslessness: the T = 0 tokens of each lane are compared with
  those of a ``vanilla`` lane (no drafts) on the same params, layout, KV
  dtype and traffic.  The chain lane must match exactly.  A tree window
  holds an accepted path's keys at other slots of the kernel's key block
  than a chain window does, and on a v5e the paged flash kernel then
  rounds some of the path's rows one or two bf16 steps otherwise, so
  near-ties can flip.  Where the tree lane differs, every greedy token of
  it and of the vanilla lane must be the best, or tied within
  ``TIE_TOL`` with the best, in a teacher-forced reference pass that
  uses no flash kernel.

Greedy speculation is lossless only if a position's logits do not depend
on how many rows the step computes.  By default XLA may skip bf16
roundings inside a fusion ("excess precision"), and which ops fuse
depends on the row count: on a v5e the 30-layer model then gives one
position logits that differ by up to 0.06 between a one-row decode and a
six-row window, enough to flip near-ties.  The script therefore turns
excess precision off for its whole process, before JAX starts; the
hidden states are then bit-identical across row counts.

Earlier lines report each phase; the last line is one JSON object naming
the device.  The script exits non-zero, without that line, if JAX finds
no TPU or any phase fails.  The persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` here.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # read once, when JAX starts its backend
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), "--xla_allow_excess_precision=false")))

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.config import SpecConfig  # noqa: E402
from repro.core.tree import TreeTemplate  # noqa: E402
from repro.data import task_prompts  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_decode import flash_decode_paged  # noqa: E402
from repro.kernels.int8_matmul import int8_matmul  # noqa: E402
from repro.kernels.smooth_quant import smooth_quant  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models.attention import _quant_kv, attend_paged  # noqa: E402
from repro.serving import GenerationRequest, ServerConfig  # noqa: E402
from repro.serving.engine import SpecEngine  # noqa: E402
from repro.serving.server import ServingLoop  # noqa: E402

ARCH = "smollm-135m"
SEED = 0
GAMMA = 5                      # chain draft length: verify window T = 6
TREE_BRANCHES = (3, 1, 1, 1)   # root-heavy: 3 root continuations, 13 nodes
SLOTS = 4                      # batch slots per lane
BLOCK = 128                    # tokens per paged KV block
PREFILL_M = 256                # rows of a prefill-sized W8A8 GEMM
HOT_T = 0.8                    # the one sampled request's temperature
# Largest |kernel - reference| over the reference's largest |value|.  The
# outputs are bf16 (one rounding is 2^-8 of a value); the int8 activation
# codes may differ by one step where the kernel's f32 division rounds
# otherwise than XLA's, which moves an output by far less.
PARITY_RTOL = 1e-2
# Phase C, tree lanes: how far (in logits) a greedy token may sit below
# the best one in the teacher-forced reference.  The logits of these
# seeded weights spread with a standard deviation near 0.5.  The
# reference attends with jnp over a contiguous cache, not with the flash
# kernel, so it rounds otherwise than a lane: a lane's token may sit just
# below a best tied with it within that rounding, and nowhere else.
TIE_TOL = 0.1
# LANES: (name, drafter, tree branches, KV cache dtype)
LANES = (("chain", "ngram", None, "bf16"),
         ("tree+int8", "ngram-tree", TREE_BRANCHES, "int8"))


def log(msg: str) -> None:
    print(msg, flush=True)


def _errors(got, want) -> tuple[float, float]:
    """Largest |got - want|, and the same over the largest |want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    return err, err / max(float(np.max(np.abs(want))), 1e-30)


# ---------------------------------------------------------------------------
# Phase A: kernel parity
# ---------------------------------------------------------------------------

def paged_case(cfg, key, variant: str):
    """Random inputs of one ``flash_decode_paged`` call at ``cfg``'s head
    widths: a verify window of every slot over a shuffled paged cache of
    ``BLOCK``-token blocks.  ``variant`` is plain, int8, tree or tree+int8.
    Returns (q, cache, block table, query positions, tree keywords)."""
    tree = TreeTemplate(TREE_BRANCHES)
    Hq, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nb = 3                                        # logical blocks per row
    N = SLOTS * nb + 1                            # + scratch block 0
    kq, kk, kv, kp, ks = jax.random.split(key, 5)
    is_tree = variant.startswith("tree")
    T = tree.num_nodes if is_tree else GAMMA + 1
    q = jax.random.normal(kq, (SLOTS, T, Hq, dh), cfg.dtype)
    k = jax.random.normal(kk, (N, BLOCK, Hkv, dh), cfg.dtype)
    v = jax.random.normal(kv, (N, BLOCK, Hkv, dh), cfg.dtype)
    table = 1 + jax.random.permutation(kp, N - 1)[: SLOTS * nb]
    bt = table.reshape(SLOTS, nb).astype(jnp.int32)
    start = jax.random.randint(ks, (SLOTS,), T, nb * BLOCK - T)
    offs = jnp.asarray(tree.depths) if is_tree else jnp.arange(T)
    qpos = (start[:, None] + offs[None, :]).astype(jnp.int32)
    cache = {"k": k, "v": v}
    if variant.endswith("int8"):
        cache["k"], cache["k_scale"] = _quant_kv(k)
        cache["v"], cache["v_scale"] = _quant_kv(v)
    kw = dict(tree_mask=jnp.asarray(tree.mask),
              win_start=start.astype(jnp.int32)) if is_tree else {}
    return q, cache, bt, qpos, kw


def phase_kernels(cfg, *, interpret: bool = False, seed: int = SEED) -> dict:
    """Kernel-vs-reference relative errors at ``cfg``'s widths; raises
    if any exceeds ``PARITY_RTOL``."""
    key = jax.random.PRNGKey(seed)
    errs = {}
    D, F = cfg.d_model, cfg.d_ff
    for label, M in (("verify", SLOTS * (GAMMA + 1)), ("prefill", PREFILL_M)):
        for K, N in ((D, F), (F, D)):
            key, kx, kw, kd, ks = jax.random.split(key, 5)
            x = jax.random.normal(kx, (M, K), cfg.dtype)
            w = jax.random.randint(kw, (K, N), -127, 128, jnp.int32
                                   ).astype(jnp.int8)
            dw = jax.random.uniform(kd, (N,), minval=1e-3, maxval=1e-2)
            s = jax.random.uniform(ks, (K,), minval=0.5, maxval=2.0)
            xq, dx = smooth_quant(x, s, interpret=interpret)
            y = int8_matmul(xq, w, dx, dw, out_dtype=x.dtype,
                            interpret=interpret)
            y_ref = ref.w8a8_matmul_ref(x, w, dw, s)
            xq_ref, _ = ref.smooth_quant_ref(x, s)
            flips = int(jnp.sum(xq != xq_ref))
            name = f"w8a8 {label} M={M} K={K} N={N}"
            err, errs[name] = _errors(y, y_ref)
            log(f"  {name}: max abs err {err:.3e}, rel {errs[name]:.3e}, "
                f"int8 codes differing {flips}/{xq.size}")

    for variant in ("plain", "int8", "tree", "tree+int8"):
        key, sub = jax.random.split(key)
        q, cache, bt, qpos, kw = paged_case(cfg, sub, variant)
        out = flash_decode_paged(q, cache["k"], cache["v"], bt, qpos,
                                 k_scale=cache.get("k_scale"),
                                 v_scale=cache.get("v_scale"),
                                 interpret=interpret, **kw)
        want = attend_paged(q, cache, bt, qpos, impl="jnp", **kw)
        name = f"flash_decode_paged {variant} T={q.shape[1]}"
        err, errs[name] = _errors(out, want)
        log(f"  {name}: max abs err {err:.3e}, rel {errs[name]:.3e}")
    bad = {n: e for n, e in errs.items() if not e <= PARITY_RTOL}
    if bad:
        raise AssertionError(f"kernel parity beyond {PARITY_RTOL}: {bad}")
    return errs


# ---------------------------------------------------------------------------
# Phases B and C: serving and losslessness
# ---------------------------------------------------------------------------

def make_traffic(vocab: int, *, n: int = 8, prompt_len=(32, 128),
                 new_tokens=(32, 64), seed: int = SEED):
    """``n`` requests from ``task_prompts`` with seeded prompt and output
    lengths; the last one samples at ``HOT_T``, the rest are greedy."""
    rng = np.random.default_rng(seed)
    prompts = task_prompts("gsm8k", n, prompt_len[1], vocab, seed=seed)
    reqs = []
    for i in range(n):
        P = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        new = int(rng.integers(new_tokens[0], new_tokens[1] + 1))
        reqs.append(GenerationRequest(
            prompts[i, :P], new, seed=seed * 1000 + i,
            temperature=HOT_T if i == n - 1 else None))
    return reqs


def lane_engine(cfg, drafter: str, branches, kv: str) -> SpecEngine:
    """The serving engine of one lane: paged KV, W8A8 verifier, T = 0."""
    model = Model(dataclasses.replace(cfg, kv_cache_dtype=kv))
    scfg = SpecConfig(gamma=GAMMA, temperature=0.0, drafter=drafter,
                      verifier="w8a8", tree_branches=branches,
                      kv_layout="paged", kv_block_size=BLOCK)
    return SpecEngine(model, scfg)


def server_config(requests) -> ServerConfig:
    return ServerConfig(
        batch_slots=SLOTS,
        max_prompt_len=max(r.prompt.size for r in requests),
        max_new_tokens=max(r.max_new_tokens for r in requests))


def serve(engine, params, requests, warmup=()) -> dict:
    """Serve ``warmup`` then ``requests`` through one ``ServingLoop`` on
    the real clock.  Raises unless every request completed, no guardrail
    fired, and the measured pass compiled nothing new."""
    loop = ServingLoop(engine, params, server_config([*warmup, *requests]))
    t0 = time.perf_counter()
    for r in warmup:
        loop.submit(r)
    loop.drain()
    warm_s = time.perf_counter() - t0
    traces = engine.step_traces
    t0 = time.perf_counter()
    handles = [loop.submit(r) for r in requests]
    loop.drain()
    serve_s = time.perf_counter() - t0
    s = loop.metrics.summary()
    c = s["counters"]
    if not (c["completed"] == c["submitted"] and c["failed"] == 0
            and c["shed"] == 0):
        errors = [repr(h.error) for h in handles if h.error is not None]
        raise AssertionError(f"requests lost: {c}; errors {errors[:3]}")
    fired = {k: n for k, n in s["robustness"].items() if n}
    if fired:
        raise AssertionError(f"guardrails fired: {fired}")
    temps = {0.0 if r.temperature is None else r.temperature
             for r in [*warmup, *requests]}
    if warmup and not engine.step_traces == traces == len(temps):
        raise AssertionError(
            f"decode step traced {traces} times in warm-up and "
            f"{engine.step_traces - traces} more while serving; expected "
            f"one per temperature ({len(temps)})")
    results = [h.result(timeout=0.0) for h in handles]
    return {
        "loop": loop,
        "tokens": [r.tokens for r in results],
        "L": float(np.mean([r.accept_len for r in results])),
        "steps": c["decode_steps"],
        "new_tokens": sum(r.new_tokens for r in results),
        "warm_s": warm_s,
        "serve_s": serve_s,
        "traces": engine.step_traces,
    }


def phase_serving(cfg, params, requests, warmup) -> dict:
    """Phase B for every lane in ``LANES``: name -> :func:`serve` result."""
    out = {}
    for name, drafter, branches, kv in LANES:
        eng = lane_engine(cfg, drafter, branches, kv)
        r = serve(eng, params, requests, warmup)
        log(f"  lane {name}: {len(requests) + len(warmup)} requests "
            f"completed, 0 failed, 0 shed, robustness counters all 0; "
            f"step traces {r['traces']} (one per temperature); "
            f"warm-up {r['warm_s']:.2f}s (compile included), serving "
            f"{r['serve_s']:.2f}s for {r['new_tokens']} tokens in "
            f"{r['steps']} steps, mean acceptance length L={r['L']:.3f}")
        out[name] = r
    return out


def reference_model(cfg, kv: str) -> tuple:
    """The model of :func:`teacher_forced` over a contiguous ``kv`` cache
    with jnp attention, so no flash kernel: (model, jitted prefill,
    jitted verify step)."""
    model = Model(dataclasses.replace(cfg, kv_cache_dtype=kv,
                                      attn_impl="jnp"))
    return model, jax.jit(model.prefill), jax.jit(model.verify_step)


def teacher_forced(reference, qparams, requests, tokens):
    """Reference logits at every greedy position.

    Each prompt but its last token is prefilled into the cache of
    ``reference`` (a :func:`reference_model`); one verify window then
    feeds the last prompt token and every generated token but the last.
    Returns one ``(len(tokens[i]), V)`` array per request: row j holds
    the logits that chose ``tokens[i][j]``."""
    model, prefill, verify = reference
    n = [len(t) for t in tokens]
    P = max(r.prompt.size for r in requests)
    prompts = np.zeros((len(requests), P - 1), np.int32)
    window = np.zeros((len(requests), max(n)), np.int32)
    for i, (r, t) in enumerate(zip(requests, tokens)):
        prompts[i, :r.prompt.size - 1] = r.prompt[:-1]
        window[i, :n[i]] = np.concatenate([r.prompt[-1:], t[:n[i] - 1]])
    start = np.array([r.prompt.size - 1 for r in requests], np.int32)
    # right padding: each row's window overwrites the padded prompt slots
    # it can see, and causality hides the rest
    cache = model.init_cache(len(requests), P + max(n))
    cache = prefill(qparams, cache, prompts)
    logits, _ = verify(qparams, cache, window, start)
    logits = np.asarray(logits)
    return [logits[i, :n[i]] for i in range(len(requests))]


def _gaps(logits, tokens) -> list:
    """How far below the best logit each token sits (0 where it is the
    argmax), per request."""
    return [lg.max(-1) - lg[np.arange(len(t)), t]
            for lg, t in zip(logits, tokens)]


def phase_lossless(cfg, params, requests, warmup, served) -> dict:
    """Phase C: the greedy tokens of each lane in ``served`` against a
    ``vanilla`` lane (no drafts) with the same layout, KV dtype and
    traffic, so the same admission caps and padded prefill lengths.

    A chain lane must match exactly.  Where a tree lane differs, every
    greedy token of it and of the vanilla lane must sit within
    ``TIE_TOL`` of the best logit of the :func:`teacher_forced` reference,
    so the two differ only where candidates tie.  Raises otherwise.
    Returns name -> list of (request index, first differing token, the
    two tokens' reference logit margin or None)."""
    greedy = [i for i, r in enumerate(requests) if r.temperature is None]
    reqs = [requests[i] for i in greedy]
    diffs = {}
    for name, _, branches, kv in LANES:
        base = serve(lane_engine(cfg, "vanilla", None, kv), params, requests,
                     warmup)["tokens"]
        base = [base[i] for i in greedy]
        mine = [served[name]["tokens"][i] for i in greedy]
        # no stop token: a request's tokens have the same length in any lane
        first = {j: int(np.argmax(a != b))
                 for j, (a, b) in enumerate(zip(mine, base))
                 if not np.array_equal(a, b)}
        diffs[name] = [(greedy[j], t, None) for j, t in first.items()]
        log(f"  lane {name} vs vanilla/{kv}: {len(reqs)} greedy requests, "
            f"{len(reqs) - len(first)} identical")
        if not first:
            continue
        if branches is None:
            raise AssertionError(
                f"lane {name}: greedy tokens differ from vanilla/{kv} at "
                f"(request, token) {[d[:2] for d in diffs[name]]}")
        qparams = lane_engine(cfg, "vanilla", None, kv).prepare_params(params)
        reference = reference_model(cfg, kv)
        g_base = _gaps(teacher_forced(reference, qparams, reqs, base), base)
        g_mine = _gaps(teacher_forced(reference, qparams, reqs, mine), mine)
        diffs[name] = [(greedy[j], t, abs(float(g_mine[j][t] - g_base[j][t])))
                       for j, t in first.items()]
        worst = max(float(np.max(g)) for g in (*g_mine, *g_base))
        log(f"    (request, first differing token, reference margin) "
            f"{[(i, t, float(f'{m:.3e}')) for i, t, m in diffs[name]]}; "
            f"both lanes' tokens within {worst:.3e} of the reference's best")
        if not worst <= TIE_TOL:
            raise AssertionError(
                f"lane {name}: a greedy token sits {worst:.3e} below the "
                f"reference's best logit, beyond a tie of {TIE_TOL}")
    return diffs


# ---------------------------------------------------------------------------
# Chip-only checks and the entry point
# ---------------------------------------------------------------------------

CACHE_HITS = []      # one entry per persistent-cache hit in this process


def _count_cache_hits(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        CACHE_HITS.append(event)


def compile_seconds(cfg, params, requests) -> str:
    """Trace and compile the chain lane's T = 0 step on a fresh engine;
    says how long each took and whether the persistent cache served it."""
    eng = lane_engine(cfg, "ngram", None, "bf16")
    loop = ServingLoop(eng, params, server_config(requests))
    lane = loop._lane(0.0, False)
    t0 = time.perf_counter()
    lowered = lane.step.lower(lane.params, lane.state)
    t1 = time.perf_counter()
    hits = len(CACHE_HITS)
    lowered.compile()
    t2 = time.perf_counter()
    source = ("from the persistent cache" if len(CACHE_HITS) > hits
              else "compiled, not in the cache")
    return f"lowering {t1 - t0:.2f}s, compile {t2 - t1:.2f}s ({source})"


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_hits)
    cfg = get_config(ARCH)
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; compile cache {cache_dir}; "
        f"XLA_FLAGS {os.environ.get('XLA_FLAGS')}")

    log("phase A: kernel parity")
    phase_kernels(cfg)

    params = Model(cfg).init_params(jax.random.PRNGKey(SEED))
    requests = make_traffic(cfg.vocab_size)
    warmup = make_traffic(cfg.vocab_size, n=2, prompt_len=(32, 48),
                          new_tokens=(8, 8), seed=SEED + 1)

    log(f"chain T=0 step, first: "
        f"{compile_seconds(cfg, params, [*warmup, *requests])}")

    log("phase B: serving through ServingLoop (paged KV, W8A8 verifier)")
    served = phase_serving(cfg, params, requests, warmup)
    for name, r in served.items():
        lane = r["loop"]._lanes[(0.0, False)]
        text = lane.step.lower(lane.params, lane.state).compile().as_text()
        n = text.count("tpu_custom_call")
        log(f"  lane {name}: compiled T=0 step holds {n} tpu_custom_call")
        if not n:
            raise AssertionError(f"lane {name}: no Mosaic kernel in the step")

    entries = sum(1 for p in Path(cache_dir).rglob("*") if p.is_file())
    log(f"chain T=0 step again: "
        f"{compile_seconds(cfg, params, [*warmup, *requests])}; "
        f"{entries} files in {cache_dir}")

    log("phase C: greedy tokens against vanilla decoding")
    phase_lossless(cfg, params, requests, warmup, served)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
