"""Pallas TPU kernel: W8A8 INT8 GEMM with INT32 MXU accumulation and a fused
per-token × per-channel dequantization epilogue (paper Eq. 8 / Eq. 10).

TPU adaptation notes (vs. the paper's Ascend NPU kernel):
  * the MXU natively consumes int8×int8→int32 via
    ``jnp.dot(..., preferred_element_type=jnp.int32)``;
  * the int32 accumulator lives in a VMEM scratch tile that is reused across
    the K grid dimension (innermost, "arbitrary" semantics), so partial sums
    never round-trip to HBM;
  * dequant scales (Δx row-block, Δw col-block) are streamed into VMEM with
    their own BlockSpecs and applied in the epilogue on the last K step —
    the FP output is written to HBM exactly once;
  * the blocks are planned from the call's shapes (:func:`plan_blocks`):
    at verify shapes one M block covers every row, so each weight tile is
    fetched once per call, and the K block divides K, so no operand is
    padded.  Blocks that overrun M or N are edge blocks of a ``pl.cdiv``
    grid, whose rows and columns past the array are never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What a plan is made for: one TPU v5e.  Its int8 MXU peak, and the block
# DMA rate and fixed cost of a grid step fitted to this kernel's device
# times under 23 block shapes (PERF.md §6).
INT8_OPS_PER_S = 393e12
HBM_BYTES_PER_S = 720e9
STEP_S = 0.5e-6
# scoped-VMEM bytes one grid step may take, with headroom under the 16 MiB
# default for the compiler's own scratch
VMEM_BUDGET = 12 * 2**20
# rows one M block covers whole: the verify windows of the serving cells
# (32 slots × 6 = 192 rows) with room to spare, and enough rows that a
# block is compute-bound on its own (2·512 int8 ops per weight byte
# against a ridge of about 480), so the weight re-reads of a split M
# cost no time
M_CAP = 512
LANES = 128


def vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """Scoped VMEM of one grid step: the double-buffered x, w and output
    blocks (output counted as f32, the widest ``out_dtype``) and scale
    blocks on their (8, 128) f32 tiles, plus the int32 accumulator and
    the dot's and the epilogue's f32 temporaries."""
    scales = 4 * (pl.cdiv(bm, 8) * 8 * LANES + 8 * pl.cdiv(bn, LANES) * LANES)
    return 2 * (bm * bk + bk * bn + bm * bn * 4 + scales) + 3 * bm * bn * 4


def _step_seconds(M: int, K: int, N: int, bm: int, bk: int, bn: int) -> float:
    """Modelled time of a call under a plan: every grid step costs the
    larger of its DMA and its MXU work plus ``STEP_S``, and the first
    blocks' fetch and the last output's write are not overlapped.  The x
    block is fetched again on every step unless it spans K, when its block
    index stays put across the N steps."""
    steps = pl.cdiv(M, bm) * pl.cdiv(N, bn) * (K // bk)
    dma = bk * bn + (0 if bk == K else bm * bk)
    step = max(dma / HBM_BYTES_PER_S, 2 * bm * bk * bn / INT8_OPS_PER_S)
    edges = (bm * bk + bk * bn + 2 * bm * bn) / HBM_BYTES_PER_S
    return steps * (step + STEP_S) + edges


def plan_blocks(M: int, K: int, N: int) -> tuple:
    """``(bm, bn, bk)`` of the call ``(M, K) x (K, N)``.

    ``bm`` is M up to ``M_CAP`` rows, else the fewest blocks of a multiple
    of 32 rows (int8's (32, 128) tile) that hold M.  ``bk`` is K or a
    multiple of 128 that divides it, so no block reads past K; ``bn`` is N
    or a multiple of 128.  Of the pairs within ``VMEM_BUDGET``, the plan
    takes the one of least modelled time (:func:`_step_seconds`)."""
    nm = pl.cdiv(M, M_CAP)
    bm = M if nm == 1 else pl.cdiv(pl.cdiv(M, nm), 32) * 32
    ks = [K] + [d for d in range(LANES, K, LANES) if K % d == 0]
    ns = [N] + list(range(LANES, N, LANES))
    fits = [(bk, bn) for bk in ks for bn in ns
            if vmem_bytes(bm, bk, bn) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(f"no W8A8 blocks of ({M}, {K}) x ({K}, {N}) fit "
                         f"{VMEM_BUDGET} bytes of VMEM")
    bk, bn = min(fits, key=lambda b: (_step_seconds(M, K, N, bm, *b),
                                      -b[0] * b[1]))
    return bm, bn, bk


def _divisor_at_most(K: int, cap: int) -> int:
    return max(d for d in range(1, min(cap, K) + 1) if K % d == 0)


def _kernel(x_ref, w_ref, dx_ref, dw_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        dx = dx_ref[...].astype(jnp.float32)   # (bm, 1)
        dw = dw_ref[...].astype(jnp.float32)   # (1, bn)
        o_ref[...] = (acc * dx * dw).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def int8_matmul(
    x_int8: jax.Array,    # (M, K) int8
    w_int8: jax.Array,    # (K, N) int8
    dx: jax.Array,        # (M,) f32
    dw: jax.Array,        # (N,) f32
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """``(x_int8 @ w_int8) * dx[:, None] * dw[None, :]`` in ``out_dtype``.

    The blocks are :func:`plan_blocks`'s; ``block_m`` / ``block_n`` /
    ``block_k`` override them (``block_k`` as the largest divisor of K not
    above it, since a K block never reads past K)."""
    M, K = x_int8.shape
    K2, N = w_int8.shape
    assert K == K2, (x_int8.shape, w_int8.shape)

    bm, bn, bk = plan_blocks(M, K, N)
    bm = bm if block_m is None else min(block_m, M)
    bn = bn if block_n is None else min(block_n, N)
    bk = bk if block_k is None else _divisor_at_most(K, block_k)
    nk = K // bk
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), nk)
    if not interpret:
        # the kernel streams the weight from HBM itself: left free, XLA
        # prefetches a small weight into VMEM ahead of the call, and the
        # read then falls outside the kernel's time on a device trace
        w_int8 = pltpu.with_memory_space_constraint(w_int8, pltpu.HBM)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="int8_matmul",
    )(x_int8, w_int8, dx[:, None], dw[None, :])
