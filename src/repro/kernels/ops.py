"""Public jit'd wrappers around the Pallas kernels, with an XLA fallback.

Dispatch policy (shared by :func:`w8a8_matmul` and :func:`flash_attend`):
  * on TPU backends the Pallas kernels run compiled;
  * on CPU the default is the XLA path, which is
    numerically identical (same int8 quantize semantics, exact int32 GEMM via
    ``dot_general(..., preferred_element_type=int32)``; same mask/online-
    softmax semantics for attention) and keeps the quantized operand int8
    in the HLO — so ``cost_analysis()`` sees the halved weight / KV-cache
    bytes exactly as the TPU kernels would;
  * ``REPRO_USE_PALLAS=1`` (or ``set_use_pallas(True)``) forces the Pallas
    kernels in ``interpret=True`` mode for validation.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.smooth_quant import smooth_quant

_FORCE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def set_use_pallas(flag: bool) -> None:
    global _FORCE_PALLAS
    _FORCE_PALLAS = flag


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attn_backend() -> str:
    """Resolved flash-attention backend under the module dispatch policy:
    ``"pallas"`` (TPU, compiled), ``"pallas-interpret"`` (forced
    validation mode) or ``"jnp"`` (CPU default)."""
    if _on_tpu():
        return "pallas"
    if _FORCE_PALLAS:
        return "pallas-interpret"
    return "jnp"


def flash_attend(
    q: jax.Array,        # (B, T, Hq, dh) decode/verify query window
    k: jax.Array,        # (B, S, Hkv, dh) contiguous KV cache (bf16/f32/int8)
    v: jax.Array,        # (B, S, Hkv, dh)
    qpos: jax.Array,     # (B, T) int32 absolute query positions
    *,
    k_scale: jax.Array | None = None,     # (B, S, Hkv) int8-KV scales
    v_scale: jax.Array | None = None,
    tree_mask: jax.Array | None = None,   # (T, T) ancestor-or-self window mask
    win_start: jax.Array | None = None,   # (B,) first window slot
    block_s: int = 512,
    force: bool = False,
) -> jax.Array:
    """Verification attention over a *contiguous* cache (slot == position).

    Same policy as :func:`w8a8_matmul`: on TPU the Pallas ``flash_decode``
    kernel runs compiled (int8 K/V stream at 1 B/elem with the scales
    folded in-kernel); ``REPRO_USE_PALLAS=1`` / ``force=True`` runs the
    kernel in interpret mode for CPU validation; the CPU default is the
    pure-jnp ``attend`` path, which is numerically identical.
    """
    if _on_tpu():
        return flash_decode(q, k, v, qpos, k_scale=k_scale, v_scale=v_scale,
                            tree_mask=tree_mask, win_start=win_start,
                            block_s=block_s)
    if _FORCE_PALLAS or force:
        return flash_decode(q, k, v, qpos, k_scale=k_scale, v_scale=v_scale,
                            tree_mask=tree_mask, win_start=win_start,
                            block_s=block_s, interpret=True)
    from repro.models.attention import attend  # lazy: avoids import cycle

    return attend(q, k, v, qpos, jnp.arange(k.shape[1], dtype=jnp.int32),
                  k_scale=k_scale, v_scale=v_scale,
                  tree_mask=tree_mask, win_start=win_start, impl="jnp")


def flash_attend_paged(
    q: jax.Array,        # (B, T, Hq, dh) decode/verify query window
    k: jax.Array,        # (N, bs, Hkv, dh) physical K block pool
    v: jax.Array,        # (N, bs, Hkv, dh) physical V block pool
    bt: jax.Array,       # (B, nb) int32 block table (logical → physical)
    qpos: jax.Array,     # (B, T) int32 absolute query positions
    *,
    k_scale: jax.Array | None = None,     # (N, bs, Hkv) int8-KV scales
    v_scale: jax.Array | None = None,
    tree_mask: jax.Array | None = None,   # (T, T) ancestor-or-self mask
    win_start: jax.Array | None = None,   # (B,) first window slot
    force: bool = False,
) -> jax.Array:
    """Verification attention over a **paged** cache (block-table
    addressed; see ``repro.core.paged_cache``).

    Same dispatch policy as :func:`flash_attend`: TPU runs the Pallas
    ``flash_decode_paged`` kernel compiled (blocks stream from their
    pool homes via scalar-prefetched table lookups — no gather
    materialisation); ``REPRO_USE_PALLAS=1`` / ``force=True`` runs it in
    interpret mode; the CPU default gathers the logical view and runs
    the numerically identical jnp ``attend``.
    """
    if _on_tpu() or _FORCE_PALLAS or force:
        return flash_decode_paged(q, k, v, bt, qpos,
                                  k_scale=k_scale, v_scale=v_scale,
                                  tree_mask=tree_mask, win_start=win_start,
                                  interpret=not _on_tpu())
    from repro.models.attention import attend_paged  # lazy: avoids cycle

    # forced jnp: attend_paged's gather-the-logical-view oracle — the
    # single implementation of the paged fallback (no second copy that
    # could drift from the bit-equality guarantee)
    cache = {"k": k, "v": v}
    if k_scale is not None:
        cache["k_scale"], cache["v_scale"] = k_scale, v_scale
    return attend_paged(q, cache, bt, qpos, tree_mask=tree_mask,
                        win_start=win_start, impl="jnp")


def w8a8_matmul(
    x: jax.Array,         # (..., K) activations (bf16/f32)
    w_int8: jax.Array,    # (K, N) int8
    w_scale: jax.Array,   # (N,) f32
    smooth: jax.Array,    # (K,) f32
) -> jax.Array:
    """Quantized-verification linear (paper §3.3): smooth→quant→int8 GEMM→dequant."""
    batch_shape = x.shape[:-1]
    K = x.shape[-1]
    N = w_int8.shape[1]
    x2 = x.reshape(-1, K)
    if _on_tpu():
        xq, dx = smooth_quant(x2, smooth)
        y = int8_matmul(xq, w_int8, dx, w_scale, out_dtype=x.dtype)
    elif _FORCE_PALLAS:
        xq, dx = smooth_quant(x2, smooth, interpret=True)
        y = int8_matmul(xq, w_int8, dx, w_scale, out_dtype=x.dtype, interpret=True)
    else:
        y = ref.w8a8_matmul_ref(x2, w_int8, w_scale, smooth, out_dtype=x.dtype)
    return y.reshape(*batch_shape, N)
