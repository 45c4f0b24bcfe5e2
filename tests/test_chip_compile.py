"""Compile the Pallas kernels of the serving path for a TPU v5e that is
described, not attached (an ahead-of-time compile against a TPU topology).

Interpret-mode tests cannot see what the Mosaic compiler refuses: block
shapes off the (8, 128) tiling, SMEM blocks that do not span their array,
or more scoped VMEM than the chip allows.  These tests lower every kernel
variant at the published widths of ``smollm-135m`` and ``quasar-paper-7b``
for one chip of a ``v5e:2x2`` topology and check that a Mosaic kernel
(``tpu_custom_call``) is in the compiled program.  Nothing runs.

The topology is described inside a module fixture, never at import time:
only one process at a time may hold the TPU library, and every pytest
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tree import TreeTemplate
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.smooth_quant import smooth_quant

# (Hq, Hkv, dh) of smollm-135m and quasar-paper-7b
HEADS = {"smollm-135m": (9, 3, 64), "quasar-paper-7b": (32, 8, 128)}
# verify M = B·(γ+1) = 4·6 rows; prefill M = one 256-token admission
VERIFY_M, PREFILL_M = 24, 256
TREE = TreeTemplate((3, 1, 1, 1))          # root-heavy, 13-node window


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("M,K,N", [
    (VERIFY_M, 576, 1536),        # smollm-135m up-projection, verify
    (PREFILL_M, 1536, 576),       # smollm-135m down-projection, prefill
    (VERIFY_M, 4096, 12288),      # quasar-paper-7b up-projection, verify
    (PREFILL_M, 12288, 4096),     # quasar-paper-7b down-projection, prefill
])
def test_int8_matmul_compiles(one_chip, M, K, N):
    hlo = _compile(int8_matmul,
                   [((M, K), jnp.int8), ((K, N), jnp.int8),
                    ((M,), jnp.float32), ((N,), jnp.float32)], one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("M,K", [
    (VERIFY_M, 1536),             # smollm-135m widest K
    (PREFILL_M, 4096),            # quasar-paper-7b d_model
    (PREFILL_M, 12288),           # quasar-paper-7b d_ff: once over VMEM
    (2048, 12288),
])
def test_smooth_quant_compiles(one_chip, M, K):
    hlo = _compile(smooth_quant, [((M, K), jnp.bfloat16),
                                  ((K,), jnp.float32)], one_chip)
    assert "tpu_custom_call" in hlo


def _flash_shapes(paged, int8, tree, heads, B=4, S=512, bs=128):
    Hq, Hkv, dh = heads
    T = TREE.num_nodes if tree else 6
    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    if paged:
        N = B * S // bs + 1
        kv = ((N, bs, Hkv, dh), kv_dt)
        sc = ((N, bs, Hkv), jnp.float32)
    else:
        kv = ((B, S, Hkv, dh), kv_dt)
        sc = ((B, S, Hkv), jnp.float32)
    shapes = [((B, T, Hq, dh), jnp.bfloat16), kv, kv]
    if paged:
        shapes.append(((B, S // bs), jnp.int32))
    shapes.append(((B, T), jnp.int32))
    if int8:
        shapes += [sc, sc]
    if tree:
        shapes += [((T, T), jnp.bool_), ((B,), jnp.int32)]
    return shapes


def _flash_fn(paged, int8, tree):
    kernel = flash_decode_paged if paged else flash_decode
    n_pos = 5 if paged else 4        # q, k, v, [bt,] qpos

    def fn(*a):
        kw = {}
        rest = list(a[n_pos:])
        if int8:
            kw["k_scale"], kw["v_scale"] = rest.pop(0), rest.pop(0)
        if tree:
            kw["tree_mask"], kw["win_start"] = rest.pop(0), rest.pop(0)
        return kernel(*a[:n_pos], **kw)
    return fn


VARIANTS = {"plain": (False, False), "int8": (True, False),
            "tree": (False, True), "tree+int8": (True, True)}


@pytest.mark.parametrize("arch", sorted(HEADS))
@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_flash_decode_compiles(one_chip, paged, variant, arch):
    int8, tree = VARIANTS[variant]
    hlo = _compile(_flash_fn(paged, int8, tree),
                   _flash_shapes(paged, int8, tree, HEADS[arch]), one_chip)
    assert "tpu_custom_call" in hlo
