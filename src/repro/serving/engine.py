"""Batched speculative serving engine over the pluggable decoding API.

``SpecEngine`` wraps the unified jitted decode step
(:func:`repro.core.spec_engine.make_decode_step`) with prompt prefill,
the generation loop, and acceptance/throughput statistics.  Drafting and
verification strategies are plugins resolved from the registries in
``repro.core.protocols``:

    engine = SpecEngine(model, SpecConfig(verifier="w8a8"))   # Quasar
    engine = SpecEngine(model, scfg, drafter="pruned")        # Table 5
    engine = SpecEngine(model, scfg, drafter=MyDrafter(...))  # custom
    engine = SpecEngine(                                      # token tree
        model, SpecConfig(tree_branches=(3, 2, 1, 1)), drafter="ngram-tree")

The verifier owns offline weight preparation: with ``verifier="w8a8"``
the engine quantizes BF16 params internally (SmoothQuant + INT8) on first
use — callers never invoke ``quantize_params`` by hand.

Two serving entry points:

* :meth:`generate` — one homogeneous batch ``(B, P)`` of prompts, shared
  token budget (the benchmark/table workhorse);
* :meth:`generate_requests` — a list of
  :class:`~repro.serving.request.GenerationRequest` with heterogeneous
  prompt lengths, budgets, seeds and temperatures, served through the
  continuous-batching :class:`~repro.serving.scheduler.Scheduler`: a
  fixed number of batch slots steps in one jit-compiled loop, and
  whenever a row exhausts its budget the next pending request is admitted
  into the freed slot via :meth:`prefill_into_slot` — the decode step
  never retraces on admission (``step_traces`` counts compilations).
  Returns per-request :class:`~repro.serving.request.RequestResult` with
  queue/service timing.

The legacy ``mode=`` constructor argument ("spec" | "vanilla" |
"pruned") remains as a deprecated shim: it maps to the matching drafter
with a passthrough BF16 verifier (params prepared by the caller), which
is exactly the seed-era behaviour.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import prng
from repro.core.config import SpecConfig
from repro.core.paged_cache import (
    SCRATCH_BLOCK,
    BlockPool,
    PrefixIndex,
    clone_block,
    init_paged_cache,
    plan_group,
    request_demand_tokens,
    scatter_prefill_rows,
    swap_in_blocks,
    swap_out_blocks,
)
from repro.core.protocols import get_drafter, get_verifier
from repro.core.spec_engine import init_state, make_decode_step
from repro.serving.request import (
    GenerationRequest,
    RequestResult,
    pad_prompt,
    safe_rate,
)
from repro.serving.metrics import AcceptanceStats
from repro.serving.scheduler import Scheduler
from repro.serving.trace import NULL_TRACER

# deprecated mode-string → drafter-registry-name mapping (public: the serve
# CLI builds its --mode choices from it)
LEGACY_MODES = {"spec": "ngram", "vanilla": "vanilla", "pruned": "pruned"}
_MAX_TEMP_STEPS = 8        # bound on per-temperature compiled-step cache
DEFAULT_BATCH_SLOTS = 8    # decode rows per scheduler loop (memory bound)


@dataclass
class GenResult:
    tokens: jnp.ndarray          # (B, S_buf) full buffers
    lengths: jnp.ndarray         # (B,)
    mean_accept_len: float       # L — committed tokens per verify step
    steps: int                   # verify steps taken
    wall_s: float
    new_tokens: int

    @property
    def tokens_per_s(self) -> float:
        # 0.0 (not a divide-by-zero spike) when a fast CPU run records
        # zero wall time
        return safe_rate(self.new_tokens, self.wall_s)


class SpecEngine:
    """Drafter x Verifier serving engine (see module docstring)."""

    def __init__(self, model, scfg: SpecConfig = SpecConfig(),
                 mode: Optional[str] = None, *,
                 drafter=None, verifier=None):
        self.model = model
        self.scfg = scfg
        self.mode = mode
        if scfg.kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_layout {scfg.kv_layout!r}; "
                             "expected 'contiguous' or 'paged'")
        if mode is not None:                       # deprecated shim
            if mode not in LEGACY_MODES:
                raise ValueError(mode)
            drafter = drafter if drafter is not None else LEGACY_MODES[mode]
            # legacy callers quantize params themselves: passthrough prepare
            verifier = verifier if verifier is not None else "bf16"
        self.drafter = get_drafter(
            drafter if drafter is not None else scfg.drafter, scfg)
        self.verifier = get_verifier(
            verifier if verifier is not None else scfg.verifier, scfg)
        # decode-step (re)compilations across all temperature variants —
        # the continuous-batching tests assert admission never bumps this
        self.step_traces = 0
        # per-group sizing of the last generate_requests call
        self.group_stats = []
        # live per drafter×verifier acceptance/step-time telemetry,
        # accumulated across generate / generate_requests / serving-lane
        # calls (bounded histograms; benchmarks/run.py reads this)
        self.telemetry = AcceptanceStats()
        self._step = self._jit_counted(
            make_decode_step(model, self.drafter, self.verifier, scfg))
        self._steps_by_temp = {}                   # temperature overrides
        self._fallback_steps = {}                  # bf16 guardrail twins
        self._prepared = None                      # (params ref, prepared)

    def _jit_counted(self, step_fn):
        """jit the decode step, counting traces (== XLA compilations)."""
        def counted(params, state):
            self.step_traces += 1      # runs at trace time only
            return step_fn(params, state)
        return jax.jit(counted)

    # ------------------------------------------------------------------
    def prepare_params(self, params, act_stats=None):
        """Offline weight preparation for this engine's verifier
        (e.g. SmoothQuant + INT8 for ``w8a8``).  Idempotent."""
        return self.verifier.prepare(self.model, params, act_stats)

    def _prepare_cached(self, params):
        # NOTE: keeps a strong reference to the last input tree as the
        # cache key, so a w8a8 engine pins the BF16 original while alive.
        # Memory-sensitive callers: params = engine.prepare_params(params)
        # once, drop the original, and pass the prepared tree (idempotent).
        if self._prepared is not None and (
                params is self._prepared[0] or params is self._prepared[1]):
            return self._prepared[1]
        self._prepared = (params, self.prepare_params(params))
        return self._prepared[1]

    def _step_for_temperature(self, t: float):
        """(jitted step, drafter) with temperature ``t`` baked in."""
        if t == self.scfg.temperature:
            return self._step, self.drafter
        if t not in self._steps_by_temp:
            if len(self._steps_by_temp) >= _MAX_TEMP_STEPS:
                # each entry pins a compiled executable — evict the oldest
                self._steps_by_temp.pop(next(iter(self._steps_by_temp)))
            scfg_t = dataclasses.replace(self.scfg, temperature=t)
            drafter = self.drafter.with_temperature(t)
            step = self._jit_counted(
                make_decode_step(self.model, drafter, self.verifier, scfg_t))
            self._steps_by_temp[t] = (step, drafter)
        return self._steps_by_temp[t]

    def fallback_step_for(self, t: float):
        """Full-precision twin of the compiled step at temperature
        ``t``: same model and drafter, bf16 (passthrough) verifier.

        The serving lane's NaN guardrail retries a tripped step through
        it with the *raw* (unprepared) params — quantized-verification
        graceful degradation: the losslessness contract enforced at
        runtime instead of assumed (docs/robustness.md).  Lazily
        compiled on first trip and cached per temperature; compilation
        bumps ``step_traces``, but only ever after a fault, so the
        no-retrace-on-admission invariant is untouched.
        """
        t = float(t)
        if t not in self._fallback_steps:
            if len(self._fallback_steps) >= _MAX_TEMP_STEPS:
                self._fallback_steps.pop(next(iter(self._fallback_steps)))
            drafter = (self.drafter if t == self.scfg.temperature
                       else self.drafter.with_temperature(t))
            scfg_t = dataclasses.replace(self.scfg, temperature=t,
                                         verifier="bf16")
            self._fallback_steps[t] = self._jit_counted(
                make_decode_step(self.model, drafter, "bf16", scfg_t))
        return self._fallback_steps[t]

    # ------------------------------------------------------------------
    def _init_state(self, params, prompts, lengths, targets, buf, key, *,
                    drafter, aux_embeds=None, draft_params=None):
        """Prefill + assemble the decode-loop state pytree."""
        B, P = prompts.shape
        assert P >= 2, "prompts must have >= 2 tokens"
        state = init_state(self.model, B, buf, key, target=targets)
        state["tokens"] = state["tokens"].at[:, :P].set(prompts)
        state["length"] = jnp.asarray(lengths, jnp.int32)
        # cache covers committed tokens *except the last* (which becomes
        # the first token of the first verify window) — hence [:, :-1]
        state["cache"] = self.model.prefill(
            params, state["cache"], prompts[:, :-1], aux_embeds=aux_embeds)
        state["drafter_state"] = drafter.init_state(
            self.model, params, prompts, buf,
            aux_embeds=aux_embeds, draft_params=draft_params)
        return state

    def _run(self, step, params, state, max_steps: int):
        """Drive the jitted step until every row reaches its target,
        feeding per-step accepted-length/wall-time telemetry (host-side,
        from the same post-step length read the loop already does)."""
        tkey = f"{self.drafter.name}:{self.verifier.name}"
        targets = np.asarray(state["target"])
        prev = np.minimum(np.asarray(state["length"]), targets)
        t0 = time.perf_counter()
        steps = 0
        while True:
            t_s = time.perf_counter()
            state = step(params, state)
            lengths = np.asarray(state["length"])
            step_s = time.perf_counter() - t_s
            steps += 1
            cur = np.minimum(lengths, targets)
            active = prev < targets
            self.telemetry.on_decode_step(
                tkey, (cur - prev)[active].tolist(), step_s)
            prev = cur
            if bool((lengths >= targets).all()):
                break
            if steps > max_steps:      # safety: >= 1 token/step guaranteed
                break
        jax.block_until_ready(state["tokens"])
        return state, time.perf_counter() - t0

    # ------------------------------------------------------------------
    def generate(
        self,
        params,
        prompts: jnp.ndarray,          # (B, P) int32
        max_new_tokens: Optional[int] = None,
        *,
        aux_embeds=None,
        key=None,
        draft_params=None,             # pruned drafting with separate params
    ) -> GenResult:
        """Homogeneous batch: shared prompt length and token budget."""
        max_new = max_new_tokens or self.scfg.max_new_tokens
        B, P = prompts.shape
        buf = P + max_new + self.drafter.gamma + 2
        key = key if key is not None else jax.random.PRNGKey(0)

        params = self._prepare_cached(params)
        lengths = jnp.full((B,), P, jnp.int32)
        targets = jnp.full((B,), P + max_new, jnp.int32)
        state = self._init_state(params, prompts, lengths, targets, buf, key,
                                 drafter=self.drafter, aux_embeds=aux_embeds,
                                 draft_params=draft_params)
        state, wall = self._run(self._step, params, state, max_new * 2 + 8)

        commits = state["stats"]["commits"]
        n_steps = int(state["stats"]["steps"])
        # per-row denominator: steps while that row was still generating
        L = float(jnp.mean(
            commits / jnp.maximum(state["stats"]["row_steps"], 1)))
        new_tokens = int(jnp.sum(jnp.minimum(state["length"], P + max_new) - P))
        return GenResult(
            tokens=state["tokens"],
            lengths=state["length"],
            mean_accept_len=L,
            steps=n_steps,
            wall_s=wall,
            new_tokens=new_tokens,
        )

    # ------------------------------------------------------------------
    # Continuous batching
    # ------------------------------------------------------------------
    def prefill_into_slot(
        self,
        params,
        state: dict,
        row: int,
        request: GenerationRequest,
        *,
        pmax: Optional[int] = None,
        drafter=None,
        aux_embeds=None,               # (1, Sa, D) — this request's slice
        draft_params=None,
        pool: Optional[BlockPool] = None,   # paged layout: the group's
        #                                     block allocator
        rid: Optional[int] = None,          # paged layout: allocator id
        #                                     (must be reserved already)
        shared_blocks: int = 0,             # prefix cache: leading blocks of
        #                                     rid's table already stored
        shared_rows: int = 0,               # ... covering this many prompt
        #                                     rows (< P; cold tail is chunked)
        tracer=None,                        # spans of each admission job
        trace_tid: int = 0,
        trace_rid: Optional[int] = None,    # request id the spans carry
    ) -> dict:
        """Admit ``request`` into slot ``row`` of a live decode state.

        Resets *every* per-row slice the decode step reads — token buffer,
        committed length, target, per-row PRNG stream, acceptance stats,
        KV/SSM cache row (freshly initialised then prefilled, so nothing
        leaks from the slot's previous occupant) and the drafter-state row
        (``Drafter.prefill_row``).  Pure host-side scatters on the state
        pytree: all shapes are unchanged, so the jitted decode step serves
        the updated state without retracing.

        With a **paged** cache (``"bt"`` in ``state["cache"]``) the cache
        reset becomes: allocate the prompt's blocks from ``pool`` under
        ``rid``'s admission-time reservation, reset the slot's
        block-table row to scratch, point its leading entries at the new
        blocks, and scatter the single-row contiguous prefill into them
        (``repro.core.paged_cache.scatter_prefill_rows``) — the prefill
        math itself is the contiguous code path, which is one of the two
        pillars of the paged-vs-contiguous bit-equality guarantee (the
        other being the position-masked read, see
        ``models/attention.attend_paged``).

        ``pmax`` fixes the padded prompt length (the serving group's
        maximum) so admission prefill compiles once per group; ``params``
        must already be prepared (``prepare_params``).  Returns a new
        state dict; the input is not mutated.

        ``tracer`` gets one span per job, each carrying ``rid``:
        ``prefill.row_init`` (row state and ``init_cache``),
        ``prefill.forward`` (prefill or warm gather + chunk),
        ``prefill.scatter`` (pool scatter and block-table patch) and
        ``prefill.drafter``.
        """
        drafter = drafter if drafter is not None else self.drafter
        P = request.prompt.size
        buf = state["tokens"].shape[1]
        pmax = P if pmax is None else pmax
        if not P <= pmax <= buf:
            raise ValueError(f"pmax {pmax} outside [{P}, {buf}]")
        tr = tracer if tracer is not None else NULL_TRACER
        targs = {"tid": trace_tid, "rid": trace_rid}
        with tr.span("prefill.row_init", **targs):
            # (1, pmax)
            prompt = jnp.asarray(pad_prompt(request.prompt, pmax))[None]

            state = dict(state)
            state["stats"] = dict(state["stats"])
            row_tokens = jnp.zeros((buf,), jnp.int32).at[:pmax].set(prompt[0])
            state["tokens"] = state["tokens"].at[row].set(row_tokens)
            state["length"] = state["length"].at[row].set(P)
            state["target"] = state["target"].at[row].set(
                P + request.max_new_tokens)
            state["key"] = prng.fill_row(state["key"], row, request.seed)
            state["stats"]["commits"] = \
                state["stats"]["commits"].at[row].set(0)
            state["stats"]["row_steps"] = \
                state["stats"]["row_steps"].at[row].set(0)
            if "bad" in state["stats"]:
                state["stats"]["bad"] = \
                    state["stats"]["bad"].at[row].set(False)

            # KV/SSM cache row: fresh init + single-row prefill, scattered in.
            # The padded prefill writes junk K/V at positions [P-1, pmax-1),
            # but verify windows cover every position gap-free before the
            # causal frontier reads it — dead weight, never live state.
            paged = "bt" in state["cache"]
            row_cache = self.model.init_cache(1, buf)
        # Warm prefix (prefix cache hit): gather the shared rows out of
        # the pool into the contiguous row cache and run *chunked*
        # prefill over the cold tail only.  The gather is an exact copy
        # (same dtype), and the chunk attends over it exactly like the
        # monolithic prefill attends over its own rows, so the admitted
        # row stays bit-identical to an unshared admission.  int8 KV
        # keeps the full recompute (attending a quantized prefix would
        # diverge from the solo run) and only skips re-*storing* the
        # shared blocks below — the capacity win without the compute
        # win.
        use_chunk = paged and self.chunked_admission(shared_rows)
        with tr.span("prefill.forward", **targs):
            if use_chunk:
                c = int(shared_rows)
                shared_ids = pool.owned(rid)[: int(shared_blocks)]
                idx = jnp.asarray(np.asarray(shared_ids, np.int32))
                warm = []
                for pool_l, row_l in zip(state["cache"]["layers"],
                                         row_cache["layers"]):
                    lay = dict(row_l)
                    for name, buf_l in pool_l.items():
                        g = jnp.take(buf_l, idx, axis=0)
                        g = g.reshape((-1,) + g.shape[2:])[:c]
                        lay[name] = row_l[name].at[0, :c].set(
                            g.astype(row_l[name].dtype))
                    warm.append(lay)
                row_cache = dict(row_cache)
                row_cache["layers"] = warm
                if P - 1 > c:
                    row_cache = self.model.prefill_chunk(
                        params, row_cache, prompt[:, c: P - 1], c)
            else:
                row_cache = self.model.prefill(
                    params, row_cache, prompt[:, :-1], aux_embeds=aux_embeds)
        with tr.span("prefill.scatter", **targs):
            if paged:                        # paged: blocks instead of a row
                if pool is None or rid is None:
                    raise ValueError("paged admission needs pool= and rid=")
                n_shared = int(shared_blocks)
                fork = n_shared > 0 and int(shared_rows) % pool.block_size != 0
                if fork:
                    # the last shared block is a partially-matched boundary:
                    # fork it copy-on-write so our tail rows never touch the
                    # donor's copy.  The "copy" is free — the scatter below
                    # rewrites the fork block wholesale (gathered shared rows
                    # + computed tail + zero pad).
                    old = pool.owned(rid)[n_shared - 1]
                    new = pool.cow(rid, old)
                    if new == old and pool.prefix is not None:
                        # sole owner (resurrected cached block): write in
                        # place, but the donor's boundary entry may claim
                        # rows beyond what we matched — drop it before we
                        # overwrite them
                        pool.prefix.evict_block(old)
                pool.alloc(rid, pool.blocks_for(P) - n_shared)
                ids = pool.owned(rid)
                w0 = n_shared - (1 if fork else 0)   # first block to write
                bt = state["cache"]["bt"].at[row].set(SCRATCH_BLOCK)
                bt = bt.at[row, : len(ids)].set(jnp.asarray(ids, jnp.int32))
                cache = dict(state["cache"])
                cache["layers"] = [
                    scatter_prefill_rows(pool_l, ids[w0:], row_l,
                                         pool.block_size, first_block=w0)
                    for pool_l, row_l in zip(cache["layers"],
                                             row_cache["layers"])]
                cache["bt"] = bt
                state["cache"] = cache
            else:
                state["cache"] = jax.tree.map(
                    lambda full, one: full.at[row].set(one[0]),
                    state["cache"], row_cache)
        # the drafter gets the UNPADDED prompt: draft-side caches may have
        # slots the drafter never rewrites (e.g. the pruned drafter skips
        # the last draft position on a full accept), so pad junk there
        # would be live — solo runs have zeros, and bit-identity demands
        # the admitted row does too
        with tr.span("prefill.drafter", **targs):
            state["drafter_state"] = drafter.prefill_row(
                self.model, params, state["drafter_state"], row,
                jnp.asarray(request.prompt, jnp.int32)[None], buf,
                aux_embeds=aux_embeds, draft_params=draft_params)
        return state

    def chunked_admission(self, shared_rows: int) -> bool:
        """Whether a paged admission whose prompt shares ``shared_rows``
        cached rows prefills only its cold tail (``prefill_chunk``)
        instead of the whole padded prompt.  int8 KV keeps the full
        recompute: attending a quantized prefix would diverge from the
        solo run."""
        return shared_rows > 0 and self.model.cfg.kv_cache_dtype != "int8"

    def _check_paged_supported(self):
        """Paged KV needs attention-family, full-causal, contiguous-slot
        caches: recurrent state cannot be paged, ring buffers already
        bound their footprint, and cross-attention caches are per-request
        constants (paging them is a ROADMAP follow-up)."""
        cfg = self.model.cfg
        if cfg.arch_type in ("ssm", "hybrid"):
            raise ValueError(
                f"kv_layout='paged' needs attention KV caches; "
                f"{cfg.arch_type!r} caches are recurrent")
        if cfg.sliding_window:
            raise ValueError(
                "kv_layout='paged' does not compose with sliding-window "
                "(ring) caches — the ring already bounds the footprint")
        if cfg.cross_attn_every or cfg.encoder_layers \
                or cfg.arch_type == "audio":
            raise ValueError(
                "kv_layout='paged' supports dense/moe self-attention "
                "stacks only (cross-attention caches are unpaged)")

    def _append_paged_blocks(self, state: dict, pool: BlockPool,
                             live: dict, gamma: int) -> dict:
        """Append-on-commit: before each decode step, top every live
        row's blocks up to its next verify window's reach
        (``length + gamma + 1`` rows, capped at the request's demand).
        Draws against the admission-time reservation, so it cannot fail
        absent fault injection; host-side ``.at[].set`` on the block
        table only — the jitted step never retraces.

        Containment: a per-slot allocation failure (the pool's
        fault-injection hook, or a genuinely broken reservation) is
        collected instead of aborting the sweep — every *other* row's
        top-up still lands, then a single
        :class:`~repro.serving.faults.RequestFault` carries the failing
        slots plus the partially-topped-up state, so the scheduler
        adopts a pool-consistent state and fails only the rows it
        names.  Partial side effects on a failing row itself are
        impossible: ``BlockPool.alloc`` is atomic (the injection hook
        runs before the free list is touched).
        """
        if not live:
            return state
        lengths = np.asarray(state["length"])
        bt = state["cache"]["bt"]
        changed = False
        failures = []
        for slot, (rid, demand_tokens) in live.items():
            need = pool.blocks_for(
                min(int(lengths[slot]) + gamma + 1, demand_tokens))
            have = len(pool.owned(rid))
            if need > have:
                try:
                    ids = pool.alloc(rid, need - have)
                except Exception as exc:  # noqa: BLE001 — containment seam
                    failures.append((slot, exc))
                    continue
                bt = bt.at[slot, have:need].set(jnp.asarray(ids, jnp.int32))
                changed = True
        if changed:
            state = dict(state)
            state["cache"] = dict(state["cache"])
            state["cache"]["bt"] = bt
        if failures:
            from repro.serving.faults import RequestFault
            raise RequestFault(
                f"block append failed for slots "
                f"{[s for s, _ in failures]}: {failures[0][1]}",
                slots=[s for s, _ in failures], state=state,
                cause=failures[0][1])
        return state

    def paged_group(self, *, num_blocks: int, block_size: int,
                    gamma: int, tracer=None,
                    trace_tid: int = 0, faults=None) -> "PagedGroup":
        """Build the per-group paged-serving context (allocator + prefix
        index + swap pool) honouring ``SpecConfig.kv_prefix_sharing``.
        ``faults`` installs a :class:`~repro.serving.faults.FaultPlan`
        on the group's allocation and swap-in seams."""
        return PagedGroup(self, num_blocks=num_blocks,
                          block_size=block_size, gamma=gamma,
                          sharing=self.scfg.kv_prefix_sharing,
                          tracer=tracer, trace_tid=trace_tid,
                          faults=faults)

    def generate_requests(
        self,
        params,
        requests: Sequence[GenerationRequest],
        *,
        batch_slots: Optional[int] = None,
        aux_embeds=None,               # (len(requests), Sa, D), request order
        draft_params=None,
        admission: str = "fifo",       # "fifo" | "edf" (deadline-aware)
        on_tokens=None,                # per-request streaming callback
        tracer=None,                   # trace.Tracer: per-group tick spans
    ) -> List[RequestResult]:
        """Serve requests with heterogeneous prompt lengths, budgets,
        seeds and temperatures; returns results in request order.

        ``admission="edf"`` orders pending admissions earliest-deadline-
        first within each priority class (``GenerationRequest.deadline_s``;
        requests without one sort last) — like ``priority`` it shifts
        ``queue_s`` only, never the tokens.  The batch API never sheds:
        every request is served even past its deadline (SLO-aware
        shedding lives in the open-loop front-end,
        ``repro.serving.server``).

        ``on_tokens(request_index, tokens)`` streams each request's
        newly-committed tokens after every decode step (``np.int32``
        deltas, indices into ``requests``); the concatenated deltas are
        bit-identical to the returned ``RequestResult.tokens``.

        Requests flow through the continuous-batching scheduler:
        ``batch_slots`` rows (default ``min(len(group), 8)``) step in one
        fixed-shape jitted loop, and finished rows are refilled from the
        pending queue mid-loop — with ``len(requests) > batch_slots`` the
        batch stays saturated instead of freezing finished rows.  Each
        request's tokens are bit-identical to serving it solo (per-row
        PRNG streams + full per-row state reset at admission).

        With ``SpecConfig(kv_layout="paged")`` the serving cache is the
        block-granular pool (``repro.core.paged_cache``): admission
        *reserves* each request's worst-case block demand instead of a
        group-max contiguous row (requests wait when the pool is full —
        head-of-line, priority order preserved), blocks are appended as
        rows commit and released at harvest, and — when ``batch_slots``
        is not forced — the slot count is sized from pool occupancy
        (the largest queued-request subset whose demands co-fit the
        pool, greedy cheapest-first), so short-request mixes get more
        concurrent rows out of the same HBM.  Token streams stay
        bit-identical to the contiguous layout (and therefore to solo
        serving) for every drafter × verifier.

        Heterogeneous *prompt lengths* require attention-family caches
        (right-padding is masked positionally); recurrent-state archs
        (ssm/hybrid) must batch equal-length prompts.
        """
        if not requests:
            return []
        t_arrival = time.perf_counter()    # queue_s counts from call time,
        #                                    across sequential temp groups
        # per-temperature-group sizing record (what was ACTUALLY
        # allocated) — benchmarks read this instead of re-deriving the
        # sizing formulas (benchmarks/ablation_kv.py paged section)
        self.group_stats = []
        params = self._prepare_cached(params)
        results: List[Optional[RequestResult]] = [None] * len(requests)

        # temperature is jit-static: group requests per effective T
        groups = {}
        for i, r in enumerate(requests):
            t = (self.scfg.temperature if r.temperature is None
                 else float(r.temperature))
            groups.setdefault(t, []).append(i)

        paged = self.scfg.kv_layout == "paged"
        if paged:
            self._check_paged_supported()
        tr = tracer if tracer is not None else NULL_TRACER
        for gi, (t, idxs) in enumerate(groups.items()):
            step, drafter = self._step_for_temperature(t)
            tr.thread_name(gi, f"group{gi} T={t:g}")
            batch = [requests[i] for i in idxs]
            lengths = [r.prompt.size for r in batch]
            if (len(set(lengths)) > 1
                    and self.model.cfg.arch_type in ("ssm", "hybrid")):
                raise ValueError(
                    f"{self.model.cfg.arch_type} caches are recurrent: "
                    "heterogeneous prompt lengths cannot be right-padded; "
                    "batch equal-length prompts")
            pmax = max(lengths)
            buf = max(r.prompt.size + r.max_new_tokens for r in batch) \
                + drafter.gamma + 2

            plan = ctx = None
            cache = None
            if paged:
                plan = plan_group(
                    lengths, [r.max_new_tokens for r in batch],
                    drafter.gamma, buf,
                    block_size=self.scfg.kv_block_size,
                    pool_blocks=self.scfg.kv_pool_blocks,
                    batch_slots=batch_slots,
                    default_slots=DEFAULT_BATCH_SLOTS)
                slots = plan.slots
                ctx = self.paged_group(num_blocks=plan.num_blocks,
                                       block_size=plan.block_size,
                                       gamma=drafter.gamma,
                                       tracer=tracer, trace_tid=gi)
                cache = init_paged_cache(self.model.cfg, slots,
                                         plan.max_blocks, plan.num_blocks,
                                         plan.block_size)
            else:
                slots = min(DEFAULT_BATCH_SLOTS if batch_slots is None
                            else batch_slots, len(batch))

            # all slots idle (length == target == 0); the scheduler admits
            keys0 = jnp.zeros((slots, 2), jnp.uint32)   # per-row streams
            state = init_state(
                self.model, slots, buf, keys0,
                drafter_state=drafter.alloc_state(
                    self.model, params, slots, buf,
                    draft_params=draft_params),
                target=jnp.zeros((slots,), jnp.int32),
                cache=cache)

            self.group_stats.append({
                "temperature": t,
                "slots": slots,
                "buf": buf,
                "kv_layout": "paged" if paged else "contiguous",
                "cache_bytes": int(sum(
                    x.nbytes for x in jax.tree.leaves(state["cache"]))),
                **({"pool_blocks": plan.num_blocks,
                    "block_size": plan.block_size} if paged else {}),
            })

            tkey = f"{drafter.name}:{self.verifier.name}"

            def group_stats_cb(accepted, step_s, n_tokens, _k=tkey):
                self.telemetry.on_decode_step(_k, accepted, step_s)

            sched = Scheduler(batch, slots, policy=admission,
                              tracer=tracer, trace_tid=gi,
                              trace_ids=idxs,
                              on_step_stats=group_stats_cb)

            def _wait(st, _gi=gi, _sched=sched):
                # step_s ends at the first sync: wait for the step here,
                # inside the scheduler's decode span
                with tr.span("step_wait", tid=_gi, step=_sched.steps):
                    jax.block_until_ready(st["length"])
                return st

            can_admit = release = preempt = None
            if paged:
                for j, i in enumerate(idxs):
                    aux = (aux_embeds[i: i + 1]
                           if aux_embeds is not None else None)
                    ctx.register(j, batch[j], aux_embeds=aux, trace_id=i)

                def admit(st, slot, j, _ctx=ctx, _drafter=drafter,
                          _pmax=pmax):
                    return _ctx.admit(st, slot, j, params=params,
                                      pmax=_pmax, drafter=_drafter,
                                      draft_params=draft_params)

                can_admit = ctx.can_admit
                release = ctx.release
                if self.scfg.kv_preempt:
                    preempt = ctx.preempt

                def step_fn(st, _s=step, _ctx=ctx, _sched=sched):
                    st = _ctx.prepare_step(st, step=_sched.steps)
                    return _wait(_s(params, st))
            else:
                def admit(st, slot, j, _idxs=idxs, _drafter=drafter,
                          _pmax=pmax):
                    i = _idxs[j]
                    aux = (aux_embeds[i: i + 1]
                           if aux_embeds is not None else None)
                    return self.prefill_into_slot(
                        params, st, slot, requests[i], pmax=_pmax,
                        drafter=_drafter, aux_embeds=aux,
                        draft_params=draft_params)

                def step_fn(st, _s=step):
                    return _wait(_s(params, st))

            group_on_tokens = None
            if on_tokens is not None:
                def group_on_tokens(j, toks, _idxs=idxs):
                    on_tokens(_idxs[j], toks)     # group -> request index

            _, group_results = sched.run(
                state, admit=admit, step=step_fn, t0=t_arrival,
                can_admit=can_admit, release=release, preempt=preempt,
                on_tokens=group_on_tokens)
            if paged:
                self.group_stats[-1].update(
                    peak_blocks=ctx.pool.peak_allocated,
                    shared_blocks=ctx.shared_blocks,
                    shared_rows=ctx.shared_rows,
                    cow_forks=ctx.cow_forks,
                    preemptions=sched.preemptions)
            for j, i in enumerate(idxs):
                results[i] = group_results[j]
        return results


def merge_state_rows(dst: dict, src: dict, rows: Sequence[int]) -> dict:
    """Graft ``rows`` of engine state ``src`` onto ``dst`` (row-sparse
    state merge — the NaN guardrail's rescue primitive).

    Contract: both states descend from the *same* pre-step state via one
    decode step each (the primary vs. the fallback execution).  Batch-
    leading leaves (tokens, length, target, key, per-row stats, drafter
    state) merge row-wise; scalar stats (``steps``) are equal in both by
    construction and kept from ``dst``.  For a paged cache the block
    table is identical in both (the jitted step never writes it), so
    the merge copies exactly the physical blocks the merged rows' table
    entries name — rows own disjoint block sets, so untouched rows'
    cache writes are preserved bit-for-bit.  Neither input is mutated.
    """
    rows = [int(r) for r in rows]
    if not rows:
        return dst
    B = dst["length"].shape[0]
    idx = jnp.asarray(rows, jnp.int32)

    def rowmerge(d, s):
        if d is s or getattr(d, "ndim", 0) < 1 or d.shape[0] != B:
            return d
        return d.at[idx].set(s[idx])

    out = dict(dst)
    for k in ("tokens", "length", "target", "key"):
        if k in dst:
            out[k] = rowmerge(dst[k], src[k])
    out["stats"] = {k: rowmerge(d, src["stats"][k])
                    for k, d in dst["stats"].items()}
    out["drafter_state"] = jax.tree.map(
        rowmerge, dst["drafter_state"], src["drafter_state"])
    if "bt" in dst["cache"]:
        bt_rows = np.asarray(dst["cache"]["bt"])[rows]
        ids = np.unique(bt_rows[bt_rows != SCRATCH_BLOCK])
        cache = dict(dst["cache"])
        if ids.size:
            bidx = jnp.asarray(ids, jnp.int32)
            cache["layers"] = jax.tree.map(
                lambda d, s: d.at[bidx].set(s[bidx]),
                dst["cache"]["layers"], src["cache"]["layers"])
        out["cache"] = cache
    else:
        out["cache"] = jax.tree.map(rowmerge, dst["cache"], src["cache"])
    return out


class PagedGroup:
    """Paged-serving context for one scheduler group: the refcounting
    :class:`~repro.core.paged_cache.BlockPool`, the prefix-cache
    :class:`~repro.core.paged_cache.PrefixIndex`, and the host-side
    ``numpy`` swap pool for preempted requests.

    Owns the scheduler-hook state machine around the jitted decode
    step — everything here is host-side bookkeeping plus ``.at[].set``
    scatters, so no hook ever retraces the step:

    * :meth:`can_admit` / :meth:`admit` — prefix-aware admission: probe
      the index, reserve only the *fresh-block* demand (minus shared
      full blocks, plus a fork for a partially-matched boundary), share
      the cached chain, prefill the cold tail (chunked), and register
      this prompt's blocks for later arrivals.  A swapped-out request
      resumes instead: re-reserve, re-alloc, copy the snapshot back.
    * :meth:`preempt` — snapshot the victim's committed cache rows and
      per-row decode state to host memory, free its blocks *now*.
    * :meth:`prepare_step` — append-on-commit block top-up plus a
      defensive copy-on-write sweep: any block in a live row's verify
      window still referenced by another request is forked before the
      step can write it.  Admission forks boundary blocks eagerly, so
      this fires only if that discipline is ever relaxed — the sweep is
      what makes "COW never mutates a shared block" an allocator
      invariant rather than a scheduling accident.
    * :meth:`release` / :meth:`drop` — exactly-once block return
      (a release racing an eviction frees nothing; regression-tested).

    The admission arithmetic degrades gracefully on tight pools: the
    boundary block is registered for sharing (which needs +1 COW
    headroom in the reservation) only when that headroom fits, so a
    pool sized for exactly one request serializes instead of
    deadlocking, and with sharing disabled every formula collapses to
    PR 5's worst-case reservation.
    """

    def __init__(self, engine: SpecEngine, *, num_blocks: int,
                 block_size: int, gamma: int, sharing: bool = True,
                 tracer=None, trace_tid: int = 0, faults=None):
        from repro.serving.faults import NULL_FAULTS, InjectedFault
        self.engine = engine
        self.gamma = int(gamma)
        self.index = PrefixIndex(block_size) if sharing else None
        self.pool = BlockPool(num_blocks, block_size, prefix=self.index)
        self.faults = faults if faults is not None else NULL_FAULTS
        if self.faults.enabled:
            def _alloc_fault(n, _f=self.faults):
                if _f.fire("alloc", blocks=int(n)):
                    raise InjectedFault(
                        f"injected BlockPool alloc failure ({n} blocks)")
            self.pool.fault_hook = _alloc_fault
        self.live: dict = {}       # slot -> (rid, demand_tokens)
        self.swap: dict = {}       # rid  -> host snapshot
        self._reqs: dict = {}      # rid  -> (request, aux_embeds)
        self._trace_ids: dict = {}  # rid -> request id the spans carry
        self._tr = tracer if tracer is not None else NULL_TRACER
        self.trace_tid = int(trace_tid)
        # telemetry (benchmarks/ablation_kv.py shared-prefix section)
        self.shared_blocks = 0     # prefix-cache block hits
        self.shared_rows = 0       # prompt rows served from cache
        self.swaps = 0             # preemptions executed
        self.cow_forks = 0         # boundary forks (admission + sweep)
        # observability counters (ServerMetrics kv_cache section via
        # :meth:`snapshot`) — admission-level prefix accounting, one
        # count per admitted request (the index's own probe counters
        # are inflated by speculative can_admit probes)
        self.prefix_hits = 0       # admissions that shared >= 1 block
        self.prefix_misses = 0     # sharing-eligible admissions, cold
        self.shared_tokens = 0     # prompt rows gathered from the cache
        self.cold_prefill_tokens = 0   # prompt rows prefilled cold
        self.padded_prefill_tokens = 0  # rows prefilled past the prompt
        self.resurrections = 0     # cached-free blocks shared back in
        self.swap_out_bytes = 0    # host-snapshot traffic, out
        self.swap_in_bytes = 0     # ... and back in
        self.swapped_out_blocks = 0
        self.swapped_in_blocks = 0

    # -- registration --------------------------------------------------
    def register(self, rid: int, request: GenerationRequest,
                 aux_embeds=None, *, trace_id: Optional[int] = None) -> None:
        """Associate ``rid`` with its request before any hook runs.
        ``trace_id`` is the id the scheduler's spans give the request
        (default ``rid``); this group's spans carry it as ``rid``."""
        self._reqs[rid] = (request, aux_embeds)
        self._trace_ids[rid] = rid if trace_id is None else int(trace_id)

    def demand_tokens(self, rid: int) -> int:
        r, _ = self._reqs[rid]
        return request_demand_tokens(r.prompt.size, r.max_new_tokens,
                                     self.gamma)

    def demand_blocks(self, rid: int) -> int:
        return self.pool.blocks_for(self.demand_tokens(rid))

    def _probe(self, rid: int):
        """(shared block ids, prompt rows they cover, cached-free count).

        Empty on a cold index, with sharing off, or when the request
        carries aux embeddings (prompt tokens alone don't determine its
        K/V content, so its blocks can neither be shared nor reused).
        """
        r, aux = self._reqs[rid]
        if self.index is None or aux is not None:
            return [], 0, 0
        ids, rows = self.index.lookup(np.asarray(r.prompt).ravel())
        n_res = sum(1 for b in ids if self.pool.ref(b) == 0)
        return ids, rows, n_res

    def _admission_need(self, rid: int):
        """(fresh-block reservation, probe) for admitting ``rid`` now.

        Graceful degradation: when the shared plan's slack cost (fresh
        blocks + a fork for a partially-matched boundary + resurrected
        cached blocks) does not fit but the plain worst-case demand
        does, the probe is discarded and the request admits *unshared*
        — a tight pool serializes exactly like PR 5 instead of
        deadlocking on sharing arithmetic.
        """
        d = self.demand_blocks(rid)
        ids, rows, n_res = self._probe(rid)
        if ids:
            fork = 1 if rows % self.pool.block_size != 0 else 0
            need = d - len(ids) + fork
            if self.pool.can_reserve(need + n_res):
                return need, (ids, rows, n_res)
        return d, ([], 0, 0)

    # -- scheduler hooks -----------------------------------------------
    def can_admit(self, rid: int) -> bool:
        if rid in self.swap:
            return self.pool.can_reserve(self.demand_blocks(rid))
        # resurrecting a cached-free block consumes one slack unit even
        # though it is not a fresh draw — count it in the gate
        need, (_, _, n_res) = self._admission_need(rid)
        return self.pool.can_reserve(need + n_res)

    def admit(self, state: dict, slot: int, rid: int, *, params,
              pmax: int, drafter, draft_params=None) -> dict:
        if rid in self.swap:
            return self._resume(state, slot, rid)
        r, aux = self._reqs[rid]
        need, (ids, rows, n_res) = self._admission_need(rid)
        P = r.prompt.size
        bs = self.pool.block_size
        # +1 COW headroom lets us *donate* our partially-filled boundary
        # block to the index (a later arrival may share it while we are
        # still decoding); skipped — not failed — when the pool is tight
        head = 1 if (self.index is not None and aux is None
                     and (P - 1) % bs != 0) else 0
        donate = bool(head) and self.pool.can_reserve(need + n_res + head)
        self.pool.reserve(rid, need + (head if donate else 0))
        if ids:
            self.pool.share(rid, ids)
            self.shared_blocks += len(ids)
            self.shared_rows += rows
        if self.index is not None and aux is None:
            if ids:
                self.prefix_hits += 1
            else:
                self.prefix_misses += 1
        cold = max(P - 1 - rows, 0)
        # the full path prefills the prompt padded to pmax; the chunk
        # path only its cold tail
        padded = 0 if self.engine.chunked_admission(rows) else pmax - P
        self.shared_tokens += rows
        self.cold_prefill_tokens += cold
        self.padded_prefill_tokens += padded
        self.resurrections += n_res
        self.live[slot] = (rid, self.demand_tokens(rid))
        if ids and rows % bs != 0:
            self.cow_forks += 1          # prefill_into_slot forks below
        trace_rid = self._trace_ids.get(rid, rid)
        with self._tr.span("prefill", tid=self.trace_tid, rid=trace_rid,
                           shared_rows=rows, cold_rows=cold,
                           padded_rows=padded):
            state = self.engine.prefill_into_slot(
                params, state, slot, r, pmax=pmax, drafter=drafter,
                aux_embeds=aux, draft_params=draft_params,
                pool=self.pool, rid=rid,
                shared_blocks=len(ids), shared_rows=rows,
                tracer=self._tr, trace_tid=self.trace_tid,
                trace_rid=trace_rid)
        if self.index is not None and aux is None:
            with self._tr.span("prefix.register", tid=self.trace_tid,
                               rid=trace_rid):
                self.index.register(np.asarray(r.prompt).ravel(),
                                    self.pool.owned(rid),
                                    include_boundary=donate)
        return state

    def release(self, state: dict, slot: int, rid: int) -> dict:
        """Harvest hook: return blocks (exactly once) and idle the row."""
        self.pool.release(rid)
        self.live.pop(slot, None)
        state = dict(state)
        state["cache"] = dict(state["cache"])
        state["cache"]["bt"] = \
            state["cache"]["bt"].at[slot].set(SCRATCH_BLOCK)
        return state

    def drop(self, rid: int) -> None:
        """Forget a request that will never resume (shed while swapped)."""
        self.swap.pop(rid, None)
        self.pool.release(rid)

    # -- preemption / swap ---------------------------------------------
    def preempt(self, state: dict, slot: int, rid: int) -> dict:
        """Evict ``slot``'s occupant to the host swap pool.

        Saves the committed cache rows ``[0, length - 1)`` (everything a
        future verify window *reads*; the window itself rewrites rows
        from ``length - 1`` on) plus every per-row decode register, then
        frees the blocks and reservation so the pending head can admit.
        Pure host work — the decode step never retraces, and the row is
        left idle (``length == target == 0``) like any un-admitted slot.
        """
        self.live.pop(slot, None)
        L = int(np.asarray(state["length"])[slot])
        n_save = self.pool.blocks_for(max(L - 1, 0))
        ids = self.pool.owned(rid)[:n_save]
        with self._tr.span("swap_out", tid=self.trace_tid,
                           rid=self._trace_ids.get(rid, rid), blocks=n_save):
            snap = {
                "n_blocks": n_save,
                "blocks": swap_out_blocks(state["cache"]["layers"], ids),
                "tokens": np.asarray(state["tokens"][slot]),
                "length": L,
                "target": int(np.asarray(state["target"])[slot]),
                "key": np.asarray(state["key"][slot]),
                "commits": int(np.asarray(state["stats"]["commits"])[slot]),
                "row_steps": int(
                    np.asarray(state["stats"]["row_steps"])[slot]),
                "drafter": jax.tree.map(lambda x: np.asarray(x[slot]),
                                        state["drafter_state"]),
            }
        self.pool.swap_out(rid)
        self.swap[rid] = snap
        self.swaps += 1
        nbytes = int(sum(x.nbytes for x in jax.tree.leaves(snap["blocks"])))
        self.swap_out_bytes += nbytes
        self.swapped_out_blocks += n_save
        state = dict(state)
        state["length"] = state["length"].at[slot].set(0)
        state["target"] = state["target"].at[slot].set(0)
        state["cache"] = dict(state["cache"])
        state["cache"]["bt"] = \
            state["cache"]["bt"].at[slot].set(SCRATCH_BLOCK)
        return state

    def _resume(self, state: dict, slot: int, rid: int) -> dict:
        """Re-admit a swapped request: fresh blocks, bit-exact copy-back."""
        with self._tr.span("swap_in", tid=self.trace_tid,
                           rid=self._trace_ids.get(rid, rid)):
            return self._resume_inner(state, slot, rid)

    def _resume_inner(self, state: dict, slot: int, rid: int) -> dict:
        snap = self.swap.pop(rid)
        if self.faults.fire("swap_in", rid=rid):
            # corrupt the host snapshot's KV payload (float leaves →
            # NaN): the resumed row decodes against poisoned state, the
            # verify-path NaN tripwire flags it, and — since the
            # corruption lives in the cache, not the params — every
            # fallback stage reproduces it, so the request fails
            # (contained) rather than silently emitting garbage.
            # int8 KV snapshots have no float leaves; the injection is
            # a no-op there (documented in docs/robustness.md).
            snap = dict(snap)
            snap["blocks"] = jax.tree.map(
                lambda x: np.full_like(x, np.nan)
                if np.issubdtype(np.asarray(x).dtype, np.floating) else x,
                snap["blocks"])
        self.pool.reserve(rid, self.demand_blocks(rid))
        ids = self.pool.alloc(rid, snap["n_blocks"])
        self.swap_in_bytes += int(sum(
            x.nbytes for x in jax.tree.leaves(snap["blocks"])))
        self.swapped_in_blocks += len(ids)
        state = dict(state)
        state["stats"] = dict(state["stats"])
        state["tokens"] = state["tokens"].at[slot].set(
            jnp.asarray(snap["tokens"]))
        state["length"] = state["length"].at[slot].set(snap["length"])
        state["target"] = state["target"].at[slot].set(snap["target"])
        state["key"] = state["key"].at[slot].set(jnp.asarray(snap["key"]))
        state["stats"]["commits"] = \
            state["stats"]["commits"].at[slot].set(snap["commits"])
        state["stats"]["row_steps"] = \
            state["stats"]["row_steps"].at[slot].set(snap["row_steps"])
        if "bad" in state["stats"]:
            state["stats"]["bad"] = \
                state["stats"]["bad"].at[slot].set(False)
        state["drafter_state"] = jax.tree.map(
            lambda full, one: full.at[slot].set(
                jnp.asarray(one).astype(full.dtype)),
            state["drafter_state"], snap["drafter"])
        cache = dict(state["cache"])
        bt = cache["bt"].at[slot].set(SCRATCH_BLOCK)
        bt = bt.at[slot, : len(ids)].set(jnp.asarray(ids, jnp.int32))
        cache["bt"] = bt
        cache["layers"] = swap_in_blocks(cache["layers"], ids,
                                         snap["blocks"])
        state["cache"] = cache
        self.live[slot] = (rid, self.demand_tokens(rid))
        return state

    # -- per-step maintenance ------------------------------------------
    def prepare_step(self, state: dict, *, step: int = 0) -> dict:
        """Run before every decode step: block top-up + COW sweep.
        ``step`` is the scheduler's step count, for the trace."""
        with self._tr.span("append_blocks", tid=self.trace_tid):
            state = self.engine._append_paged_blocks(
                state, self.pool, self.live, self.gamma)
        if self.index is None or not self.live:
            return state
        with self._tr.span("cow_sweep", tid=self.trace_tid, step=step):
            return self._cow_sweep(state)

    def _cow_sweep(self, state: dict) -> dict:
        """Defensive copy-on-write: fork any still-shared block the next
        verify window would write (rows [L-1, L+gamma])."""
        bt = state["cache"]["bt"]
        bt_host = np.asarray(bt)
        lengths = np.asarray(state["length"])
        layers = state["cache"]["layers"]
        bs = self.pool.block_size
        changed = False
        for slot, (rid, _demand) in self.live.items():
            L = int(lengths[slot])
            lo = max(L - 1, 0) // bs
            hi = min((L + self.gamma) // bs, bt_host.shape[1] - 1)
            for k in range(lo, hi + 1):
                bid = int(bt_host[slot, k])
                if bid == SCRATCH_BLOCK or self.pool.ref(bid) <= 1:
                    continue
                new = self.pool.cow(rid, bid)
                layers = clone_block(layers, bid, new)
                bt = bt.at[slot, k].set(new)
                self.cow_forks += 1
                changed = True
        if changed:
            state = dict(state)
            state["cache"] = dict(state["cache"])
            state["cache"]["layers"] = layers
            state["cache"]["bt"] = bt
        return state

    # -- observability -------------------------------------------------
    def snapshot(self) -> dict:
        """Gauge snapshot for ``ServerMetrics.add_kv_source`` (schema:
        docs/observability.md, kv_cache section).  All counters are
        monotone; ``pool`` carries this group's point-in-time gauges."""
        return {
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "shared_blocks": self.shared_blocks,
            "shared_tokens": self.shared_tokens,
            "cold_prefill_tokens": self.cold_prefill_tokens,
            "padded_prefill_tokens": self.padded_prefill_tokens,
            "cow_forks": self.cow_forks,
            "resurrections": self.resurrections,
            "cached_evicted": self.pool.counters["cached_evicted"],
            "swap_out_blocks": self.swapped_out_blocks,
            "swap_in_blocks": self.swapped_in_blocks,
            "swap_out_bytes": self.swap_out_bytes,
            "swap_in_bytes": self.swap_in_bytes,
            "preemptions": self.swaps,
            "pool": {
                "capacity": self.pool.capacity,
                "free": self.pool.free_blocks,
                "cached": self.pool.cached_blocks,
                "unique_allocated": self.pool.unique_allocated,
                "peak_allocated": self.pool.peak_allocated,
            },
        }

    # -- invariants ----------------------------------------------------
    def check_invariants(self) -> None:
        self.pool.check_invariants()
        for slot, (rid, _d) in self.live.items():
            assert rid not in self.swap, (
                f"request {rid} both live and swapped")
