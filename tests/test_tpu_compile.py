"""Compile the arena scan kernels for a described TPU v5e at real widths.

Nothing runs: each test lowers and compiles ``ops._segmented_topk`` with
the Pallas kernels (``interpret=False``) for one chip of a ``v5e:2x2``
topology that is described, not attached — what the chip's compiler would
refuse (tiling, unsupported casts, VMEM) fails here, at no chip time.
Shapes: the paper's base size, N = 1M rows x D = 128, k = 10.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch import compile_cache, roofline

N, D, W, K = 1_000_000, 128, 4, 10
R = 3_000_000          # CSR row-table entries (Σ|I| of a selection)
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == KIND
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    with compile_cache.off():
        yield SingleDeviceSharding(topo.devices[0])


def compile_scan(sharding, *, q, lmax, dtype="f32", tomb=False,
                 rerank=False, fused=True):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    f32 = jnp.float32
    chunk, qtile = min(ops.SEG_CHUNK, lmax), None
    if fused:
        tc = roofline.fused_scan_tiles(D, lmax, dtype, q, backend="pallas",
                                       label_words=W, device_kind=KIND)
        chunk, qtile = tc.rows_per_chunk, tc.queries_per_tile
    int8 = dtype == "int8"
    args = (s((q, D), f32), s((q, W), jnp.int32),
            s((N, D), jnp.uint8 if int8 else f32), s((N, W), jnp.int32),
            s((N,), f32), s((R,), jnp.int32), s((q,), jnp.int32),
            s((q,), jnp.int32),
            s(((N + 7) // 8,), jnp.uint8) if tomb else None,
            s((N,), f32) if int8 else None, s((N,), f32) if int8 else None,
            s((N, D), f32) if rerank else None,
            s((N,), f32) if rerank else None)
    compiled = ops._segmented_topk.lower(
        *args, k=K, lmax=lmax, chunk=chunk, metric="l2", backend="pallas",
        interpret=False, dtype=dtype, dcols=D if int8 else None,
        fused=fused, qtile=qtile).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


SHAPES = [(8, 4096), (64, 65536)]


@pytest.mark.parametrize("q,lmax", SHAPES)
def test_fused_f32_compiles(one_chip, q, lmax):
    compile_scan(one_chip, q=q, lmax=lmax)


@pytest.mark.parametrize("q,lmax", SHAPES)
def test_fused_int8_rerank_compiles(one_chip, q, lmax):
    compile_scan(one_chip, q=q, lmax=lmax, dtype="int8", rerank=True)


@pytest.mark.parametrize("q,lmax", SHAPES)
def test_fused_f32_tombstones_compiles(one_chip, q, lmax):
    compile_scan(one_chip, q=q, lmax=lmax, tomb=True)


@pytest.mark.parametrize("q,lmax", SHAPES)
def test_unfused_gather_compiles(one_chip, q, lmax):
    compile_scan(one_chip, q=q, lmax=lmax, fused=False)


def test_vmem_entry_is_the_compiler_limit(one_chip):
    """The tile model's VMEM budget for the chip is the limit its compiler
    enforces: half of it compiles, one MiB over is refused by name."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    limit = roofline.VMEM_BYTES[KIND]

    def compile_with_scratch(nbytes):
        def kernel(x_ref, o_ref, s_ref):
            s_ref[0:8, :] = x_ref[...]
            o_ref[...] = s_ref[0:8, :]
        call = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((nbytes // 512, 128), jnp.float32)])
        x = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=one_chip)
        return jax.jit(call).lower(x).compile()

    compile_with_scratch(limit // 2)
    with pytest.raises(Exception, match=f"size={limit}"):
        compile_with_scratch(limit + 2**20)


def test_in_place_root_tier_compiles_to_slices(one_chip):
    """The benchmark cell's root tier (256 queries over the whole 1M-row
    arena, span 2^20) on the XLA body, read in place: the arena is read
    by contiguous slices, the only gather left is the merge's [Q, k]
    take_along_axis, and the distance stays the f32 multiply + minor-axis
    reduce (no dot, no convolution)."""
    import re

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, f32, i32 = 256, jnp.float32, jnp.int32
    text = ops._segmented_topk.lower(
        s((q, D), f32), s((q, W), i32), s((N, D), f32), s((N, W), i32),
        s((N,), f32), None, s((q,), i32), s((q,), i32),
        k=K, lmax=1 << 20, chunk=ops.SEG_CHUNK, metric="l2", backend="ref",
        interpret=False, in_place=True).compile().as_text()
    assert not re.search(r"\b(dot|convolution)\(", text)
    gathers = re.findall(r'= \S+ gather\(.*op_name="([^"]*)"', text)
    assert gathers and all("scan.merge" in g for g in gathers), gathers
    for scope in ("scan.rows", "scan.norms", "scan.label_words"):
        assert f"{scope}/dynamic_slice" in text, scope
