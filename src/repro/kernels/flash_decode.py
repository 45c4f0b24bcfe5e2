"""Pallas TPU kernel: flash attention for the speculative *verify/decode*
step — a short query window (T = 1…γ+1) attending a long contiguous KV
cache with online softmax over cache blocks.

This is the attention hot-spot of Quasar's verification pass at long
context (EXPERIMENTS §Roofline: decode_32k memory term is cache-read
dominated).  Design:

* grid = (B, Hkv, S/block_s); the S dimension is innermost/"arbitrary" so
  the (m, l, acc) running-softmax state lives in VMEM scratch across cache
  blocks and the output is written exactly once;
* all G = Hq/Hkv grouped query heads of one kv head are processed together
  (rows = G·T ≤ a few dozen — one VREG tile);
* causality against the cache: slot index == absolute position
  (contiguous cache layout), masked against the per-(row, t) query
  positions streamed in as an int32 block;
* **int8 KV cache** (``k_scale``/``v_scale``): K/V blocks stay int8 all
  the way into VMEM — the HBM cache-read traffic is halved — and the
  per-(token, head) symmetric scales are streamed as their own (1, bs)
  f32 blocks.  They are folded into the online softmax exactly as the
  jnp oracle does: scores are scaled per key *column* before masking,
  probabilities are scaled before the ``p·v`` product but **after** the
  running ``l`` sum (the softmax normaliser must see unscaled mass);
* **token-tree windows** (``tree_mask``/``win_start``): the T window
  tokens occupy cache slots ``[win_start, win_start + T)`` in packed node
  order while ``qpos`` carries ``win_start + depth``.  Inside that slot
  range the template's ancestor-or-self mask replaces position causality.
  The per-column ancestor bit is gathered MXU-style — a (GT, T) mask
  matmul against a (T, block_s) relative-slot one-hot — so the kernel
  needs no dynamic gathers.  Tree windows compose with int8 KV: the
  quantized verify path is the tree path with scales folded in.

* **paged KV cache** (:func:`flash_decode_paged`): K/V live in physical
  block pools ``(num_blocks, block_size, Hkv, dh)`` shared by all rows,
  and a per-row block table maps logical block ``s`` to its physical
  block.  The table rides in as a **scalar-prefetch** operand
  (``pltpu.PrefetchScalarGridSpec``), so the K/V/scale BlockSpec
  index_maps dereference it — ``(bt[b, s], h, 0, 0)`` — and the blocks
  stream straight from their pool homes with *no gather materialisation*.
  The grid's S dimension walks logical blocks, so the softmax body is
  byte-for-byte the contiguous ``_flash_body`` (logical position =
  ``s * block_size + lane``); int8 scales and tree masks compose
  unchanged.

The pure-jnp oracle is the ``attend`` path in models/attention.py (which
accepts the same ``k_scale``/``v_scale``/``tree_mask``/``win_start``);
the paged oracle gathers the logical view first
(``repro.core.paged_cache.gather_block_rows``).  Tests sweep shapes and
templates and assert allclose in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VAL = -1e30


def kernel_name(paged: bool, int8: bool, tree: bool) -> str:
    """The ``pallas_call`` name of a variant: ``flash_decode`` or
    ``flash_decode_paged``, then ``_tree`` and ``_int8`` as they apply.
    Only the paged variants contain ``flash_decode_paged``, so a trace
    tells the two layouts apart by that substring."""
    return ("flash_decode" + ("_paged" if paged else "")
            + ("_tree" if tree else "") + ("_int8" if int8 else ""))


def _flash_body(q_ref, k_ref, v_ref, qpos_ref, ks_ref, vs_ref, tm_ref, ws_ref,
                o_ref, m_ref, l_ref, acc_ref,
                *, ns: int, block_s: int, scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VAL)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (GT, dh)
    k = k_ref[0, 0].astype(jnp.float32)           # (bs, dh) — int8 upcast in VMEM
    v = v_ref[0, 0].astype(jnp.float32)           # (bs, dh)
    qpos = qpos_ref[0]                            # (GT, 1) int32

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (GT, bs)
    if ks_ref is not None:
        # int8 KV: per-(token, head) key scale folded into the score columns
        s = s * ks_ref[0, 0]                      # (1, bs) broadcast over rows
    kpos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos <= qpos                          # slot==position causality
    if tm_ref is not None:
        T = tm_ref.shape[-1]
        ws = ws_ref[pl.program_id(0)]             # this row's window start
        rel = kpos - ws                           # (GT, bs) row-invariant
        in_win = (rel >= 0) & (rel < T)
        # ancestor gather as a matmul: onehot[j, c] = (slot_c - ws == j)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (T, block_s), 0)
                  == (jax.lax.broadcasted_iota(jnp.int32, (T, block_s), 1)
                      + s_idx * block_s - ws)).astype(jnp.float32)
        anc = jnp.dot(tm_ref[0, 0], onehot,
                      preferred_element_type=jnp.float32) > 0.5  # (GT, bs)
        # boolean select as logic: Mosaic cannot lower a select of i1
        valid = (in_win & anc) | (~in_win & valid)
    s = jnp.where(valid, s, MASK_VAL)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if vs_ref is not None:
        # value scale folds into the probabilities *after* the l sum — the
        # normaliser must accumulate unscaled probability mass
        p = p * vs_ref[0, 0]                      # (1, bs)
    acc_new = acc_prev * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(s_idx == ns - 1)
    def _epilogue():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, qpos_ref, o_ref, m_ref, l_ref, acc_ref,
            *, ns: int, block_s: int, scale: float):
    """Plain chain window over a bf16/f32 contiguous cache."""
    _flash_body(q_ref, k_ref, v_ref, qpos_ref, None, None, None, None,
                o_ref, m_ref, l_ref, acc_ref,
                ns=ns, block_s=block_s, scale=scale)


def _kernel_tree(q_ref, k_ref, v_ref, qpos_ref, tm_ref, ws_ref,
                 o_ref, m_ref, l_ref, acc_ref,
                 *, ns: int, block_s: int, scale: float):
    """Tree-masked window (ancestor mask + window start) over bf16/f32."""
    _flash_body(q_ref, k_ref, v_ref, qpos_ref, None, None, tm_ref, ws_ref,
                o_ref, m_ref, l_ref, acc_ref,
                ns=ns, block_s=block_s, scale=scale)


def _kernel_int8(q_ref, k_ref, v_ref, qpos_ref, ks_ref, vs_ref,
                 o_ref, m_ref, l_ref, acc_ref,
                 *, ns: int, block_s: int, scale: float):
    """Chain window over an int8 cache (per-(token, head) scale refs)."""
    _flash_body(q_ref, k_ref, v_ref, qpos_ref, ks_ref, vs_ref, None, None,
                o_ref, m_ref, l_ref, acc_ref,
                ns=ns, block_s=block_s, scale=scale)


def _kernel_tree_int8(q_ref, k_ref, v_ref, qpos_ref, ks_ref, vs_ref,
                      tm_ref, ws_ref, o_ref, m_ref, l_ref, acc_ref,
                      *, ns: int, block_s: int, scale: float):
    """Tree-masked window over an int8 cache — the fully-loaded variant."""
    _flash_body(q_ref, k_ref, v_ref, qpos_ref, ks_ref, vs_ref, tm_ref, ws_ref,
                o_ref, m_ref, l_ref, acc_ref,
                ns=ns, block_s=block_s, scale=scale)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(
    q: jax.Array,        # (B, T, Hq, dh) query window
    k: jax.Array,        # (B, S, Hkv, dh) contiguous KV cache (bf16/f32 or int8)
    v: jax.Array,        # (B, S, Hkv, dh)
    qpos: jax.Array,     # (B, T) int32 absolute query positions
    *,
    k_scale: jax.Array | None = None,     # (B, S, Hkv) f32 int8-KV scales
    v_scale: jax.Array | None = None,     # (B, S, Hkv)
    tree_mask: jax.Array | None = None,   # (T, T) bool ancestor-or-self
    win_start: jax.Array | None = None,   # (B,) int32 first window slot
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Flash verification attention over a *contiguous* KV cache.

    Args / shapes:
      q          ``(B, T, Hq, dh)``   verify/decode query window
                 (T = 1…γ+1; Hq must be a multiple of Hkv — GQA groups
                 are processed together per kv head);
      k, v       ``(B, S, Hkv, dh)``  the cache buffers, slot index ==
                 absolute position; bf16/f32, or int8 with scales;
      qpos       ``(B, T)`` int32     absolute query positions (per-row
                 ``start + arange`` for chains, ``start + depth`` for
                 tree windows);
      k_scale, v_scale  ``(B, S, Hkv)`` f32 — per-(token, head) int8-KV
                 scales; pass both or neither;
      tree_mask  ``(T, T)`` bool      ancestor-or-self mask of a packed
                 tree window (requires ``win_start (B,) int32``);
      block_s    cache-block tile size (S is zero-padded to a multiple;
                 pad slots sit at positions ≥ S, masked by causality);
      interpret  run the kernel in Pallas interpret mode (CPU parity).

    Returns ``(B, T, Hq, dh)`` in ``q.dtype`` — numerically equal
    (≤1e-5, f32 accumulation) to the jnp oracle
    ``models.attention.attend``.
    """
    B, T, Hq, dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    GT = G * T
    scale = dh ** -0.5
    tree = tree_mask is not None
    if tree and win_start is None:
        raise ValueError("tree_mask requires win_start")
    int8 = k_scale is not None
    if int8 != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")

    bs = min(block_s, S)
    Sp = (-S) % bs + S
    if Sp != S:  # pad slots sit at positions >= S and are masked by qpos
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        if int8:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, Sp - S), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, Sp - S), (0, 0)))
    ns = Sp // bs

    # (B, Hkv, GT, dh): group the G query heads of each kv head
    qg = q.reshape(B, T, Hkv, G, dh).transpose(0, 2, 3, 1, 4).reshape(B, Hkv, GT, dh)
    kk = k.transpose(0, 2, 1, 3)                  # (B, Hkv, Sp, dh)
    vv = v.transpose(0, 2, 1, 3)
    # per-row query positions, broadcast over G
    qp = jnp.repeat(qpos[:, None, :], G, axis=1).reshape(B, GT, 1)

    in_specs = [
        pl.BlockSpec((1, 1, GT, dh), lambda b, h, s: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, dh), lambda b, h, s: (b, h, s, 0)),
        pl.BlockSpec((1, 1, bs, dh), lambda b, h, s: (b, h, s, 0)),
        pl.BlockSpec((1, GT, 1), lambda b, h, s: (b, 0, 0)),
    ]
    operands = [qg, kk, vv, qp]
    if int8:
        # (B, Hkv, 1, Sp): one scale row per cache block, broadcast over
        # the GT score rows inside the kernel
        ksc = k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        vsc = v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        spec = pl.BlockSpec((1, 1, 1, bs), lambda b, h, s: (b, h, 0, s))
        in_specs += [spec, spec]
        operands += [ksc, vsc]
    if tree:
        # ancestor rows repeated per grouped head: GT index = g*T + t
        tm = jnp.tile(tree_mask.astype(jnp.float32), (G, 1))   # (GT, T)
        in_specs.append(
            pl.BlockSpec((1, 1, GT, T), lambda b, h, s: (0, 0, 0, 0)))
        # the whole (B,) vector sits in SMEM: a rank-1 block must span
        # the array (or a multiple of 128), so no per-row (1,) block
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands += [tm[None, None], win_start.astype(jnp.int32)]
        kernel_fn = _kernel_tree_int8 if int8 else _kernel_tree
    else:
        kernel_fn = _kernel_int8 if int8 else _kernel
    kernel = functools.partial(kernel_fn, ns=ns, block_s=bs, scale=scale)

    out_dtype = q.dtype
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, GT, dh), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, GT, dh), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((GT, 1), jnp.float32),
            pltpu.VMEM((GT, 1), jnp.float32),
            pltpu.VMEM((GT, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=kernel_name(False, int8, tree),
    )(*operands)

    # (B, Hkv, GT, dh) → (B, T, Hq, dh)
    return out.reshape(B, Hkv, G, T, dh).transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, dh)


# ---------------------------------------------------------------------------
# Paged (block-table) variant
# ---------------------------------------------------------------------------

def _make_paged_kernel(int8: bool, tree: bool):
    """Kernel shim: route the scalar-prefetched block table (consumed by
    the BlockSpec index_maps, unused in the body) and the optional
    int8-scale / tree-mask refs into the shared ``_flash_body``."""
    def kernel(bt_ref, *refs, ns, block_s, scale):
        del bt_ref                     # only the index_maps dereference it
        q_ref, k_ref, v_ref, qpos_ref = refs[:4]
        i = 4
        ks_ref = vs_ref = tm_ref = ws_ref = None
        if int8:
            ks_ref, vs_ref = refs[i: i + 2]
            i += 2
        if tree:
            tm_ref, ws_ref = refs[i: i + 2]
            i += 2
        o_ref, m_ref, l_ref, acc_ref = refs[i: i + 4]
        _flash_body(q_ref, k_ref, v_ref, qpos_ref, ks_ref, vs_ref,
                    tm_ref, ws_ref, o_ref, m_ref, l_ref, acc_ref,
                    ns=ns, block_s=block_s, scale=scale)
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(
    q: jax.Array,        # (B, T, Hq, dh) query window
    k: jax.Array,        # (N, bs, Hkv, dh) physical K block pool
    v: jax.Array,        # (N, bs, Hkv, dh) physical V block pool
    bt: jax.Array,       # (B, nb) int32 block table (logical → physical)
    qpos: jax.Array,     # (B, T) int32 absolute query positions
    *,
    k_scale: jax.Array | None = None,     # (N, bs, Hkv) f32 int8-KV scales
    v_scale: jax.Array | None = None,     # (N, bs, Hkv)
    tree_mask: jax.Array | None = None,   # (T, T) bool ancestor-or-self
    win_start: jax.Array | None = None,   # (B,) int32 first window slot
    interpret: bool = False,
) -> jax.Array:
    """Flash verification attention over a **paged** KV cache.

    Identical online-softmax math to :func:`flash_decode`; the only
    difference is *addressing*: the grid's innermost dimension walks the
    ``nb`` logical blocks of each row's sequence, and the K/V (+ scale)
    BlockSpec index_maps look the physical block up in the
    scalar-prefetched table — ``(bt[b, s], h, 0, 0)`` — so each block
    streams HBM→VMEM from its pool home.  Logical key positions are
    reconstructed in-kernel as ``s * block_size + lane``, which keeps
    slot==position causality, tree-window masking and int8 scale folding
    byte-identical to the contiguous kernel.  Returns ``(B, T, Hq, dh)``.
    """
    B, T, Hq, dh = q.shape
    N, bs, Hkv, _ = k.shape
    nb = bt.shape[1]
    G = Hq // Hkv
    GT = G * T
    scale = dh ** -0.5
    tree = tree_mask is not None
    if tree and win_start is None:
        raise ValueError("tree_mask requires win_start")
    int8 = k_scale is not None
    if int8 != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")

    # (B, Hkv, GT, dh): group the G query heads of each kv head
    qg = q.reshape(B, T, Hkv, G, dh).transpose(0, 2, 3, 1, 4).reshape(B, Hkv, GT, dh)
    kk = k.transpose(0, 2, 1, 3)                  # (N, Hkv, bs, dh)
    vv = v.transpose(0, 2, 1, 3)
    qp = jnp.repeat(qpos[:, None, :], G, axis=1).reshape(B, GT, 1)

    in_specs = [
        pl.BlockSpec((1, 1, GT, dh), lambda b, h, s, bt_ref: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, dh),
                     lambda b, h, s, bt_ref: (bt_ref[b, s], h, 0, 0)),
        pl.BlockSpec((1, 1, bs, dh),
                     lambda b, h, s, bt_ref: (bt_ref[b, s], h, 0, 0)),
        pl.BlockSpec((1, GT, 1), lambda b, h, s, bt_ref: (b, 0, 0)),
    ]
    operands = [qg, kk, vv, qp]
    if int8:
        ksc = k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        vsc = v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        spec = pl.BlockSpec((1, 1, 1, bs),
                            lambda b, h, s, bt_ref: (bt_ref[b, s], h, 0, 0))
        in_specs += [spec, spec]
        operands += [ksc, vsc]
    if tree:
        tm = jnp.tile(tree_mask.astype(jnp.float32), (G, 1))   # (GT, T)
        in_specs.append(pl.BlockSpec((1, 1, GT, T),
                                     lambda b, h, s, bt_ref: (0, 0, 0, 0)))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands += [tm[None, None], win_start.astype(jnp.int32)]
    kernel = functools.partial(_make_paged_kernel(int8, tree),
                               ns=nb, block_s=bs, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, GT, dh),
                               lambda b, h, s, bt_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((GT, 1), jnp.float32),
            pltpu.VMEM((GT, 1), jnp.float32),
            pltpu.VMEM((GT, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, GT, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=kernel_name(True, int8, tree),
    )(bt.astype(jnp.int32), *operands)

    # (B, Hkv, GT, dh) → (B, T, Hq, dh)
    return out.reshape(B, Hkv, G, T, dh).transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, dh)
