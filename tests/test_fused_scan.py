"""Fused scan kernel (ISSUE 10 tentpole, DESIGN.md §3.9).

Pins the fused-stage contract:

  1. **oracle parity** — the fused scan stage (``fused=True``) is
     positions/gids-BITWISE against the unchunked oracle
     ``ref.segmented_filtered_topk`` for every storage spec, every chunk
     size that divides the span, and every query-tile width.  On
     tie-heavy integer data (f32-exact arithmetic) the distances are
     bitwise too — the merge order, not just the set, is pinned;
  2. **fused=False identity** — the flag default runs the pre-existing
     executor program: same static signature, same results bit for bit;
  3. **pallas kernel** — the Pallas implementation (interpret mode off
     TPU) matches the same oracle on small shapes across all storage
     specs, including tombstones and the int8 ``dcols`` lane-mask hazard
     (lane padding to 128 dequantizes to the row zero-point unless
     masked);
  4. **delta scans** — ``delta_topk(fused=True)`` equals the unfused
     delta program (the streaming merge consumes identical inputs);
  5. **tile model** — ``launch/roofline.py::fused_scan_tiles`` is
     deterministic per (D, span tier, dtype, Q-bucket, backend, device
     kind) — the property the serving zero-retrace invariant rests on —
     and the measured-autotune override takes precedence once pinned.

Each parity property is a plain ``check_*`` function driven by pinned
examples (always run) and, when hypothesis is importable, by generated
cases (the container may lack hypothesis).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.base import quantize_int8
from repro.kernels import ops, ref
from repro.kernels.fused_scan import clamp_qtile, resolve_fused
from repro.launch import roofline

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYP = True
except ImportError:  # container without hypothesis: pinned examples only
    HAVE_HYP = False


# ---------------------------------------------------------------------------
# case builder: one segmented-search problem per (storage spec, seed)
# ---------------------------------------------------------------------------

SPECS = ("f32", "fp16", "int8", "fp16+rerank", "int8+rerank")


def make_case(spec: str, *, N=400, D=24, Q=16, W=2, lmax=32, seed=0,
              integer=True, tomb=True, tail=False):
    """Build raw segmented_topk operands for ``spec``.

    ``integer=True`` draws small-integer vectors: every distance is exact
    in f32, ties abound, and bitwise assertions pin the (distance,
    position) ORDER of the merge rather than accidentally passing on
    distinct values.  ``tail=True`` makes the first three segments end at
    the row table's end, with lengths no power of two divides: their last
    chunk is partial and its id window overhangs the table."""
    rng = np.random.default_rng(seed)
    if integer:
        xf = rng.integers(-4, 5, (N, D)).astype(np.float32)
        q = rng.integers(-4, 5, (Q, D)).astype(np.float32)
    else:
        xf = rng.standard_normal((N, D)).astype(np.float32)
        q = rng.standard_normal((Q, D)).astype(np.float32)
    alw = rng.integers(0, 2, (N, W)).astype(np.int32)
    lq = np.zeros((Q, W), np.int32)
    lq[:, 0] = 1
    rows = rng.integers(0, N, (Q * lmax,)).astype(np.int32)
    starts = (np.arange(Q) * lmax).astype(np.int32)
    lens = rng.integers(0, lmax + 1, (Q,)).astype(np.int32)
    if tail:
        lens[:3] = (lmax - 3, 5, lmax - 1)
        starts[:3] = Q * lmax - lens[:3]
    tb = (rng.integers(0, 256, ((N + 7) // 8,)).astype(np.uint8)
          if tomb else None)

    dtype = spec.split("+")[0]
    kw = dict(metric="l2", lmax=lmax, dtype=dtype)
    if dtype == "f32":
        ax, axn = xf, np.sum(xf * xf, axis=1).astype(np.float32)
    elif dtype == "fp16":
        ax = xf.astype(np.float16)
        xd = ax.astype(np.float32)
        axn = np.sum(xd * xd, axis=1).astype(np.float32)
    else:
        ax, scale, zero = quantize_int8(xf)
        xd = zero[:, None] + scale[:, None] * ax.astype(np.float32)
        axn = np.sum(xd * xd, axis=1).astype(np.float32)
        kw.update(scales=jnp.asarray(scale), zeros=jnp.asarray(zero))
    if spec.endswith("+rerank"):
        kw.update(rerank=jnp.asarray(xf), kprime=8,
                  rerank_norms=jnp.asarray(
                      np.sum(xf * xf, axis=1).astype(np.float32)))
    args = (jnp.asarray(q), jnp.asarray(lq), jnp.asarray(ax),
            jnp.asarray(alw), jnp.asarray(axn), jnp.asarray(rows),
            starts, lens)
    return args, (None if tb is None else jnp.asarray(tb)), kw


def oracle(args, tomb, kw, k):
    return ref.segmented_filtered_topk(
        *[jnp.asarray(a) for a in args], k=k, tomb=tomb, **kw)


# ---------------------------------------------------------------------------
# 1 + 2: lax fused stage vs oracle and vs fused=False, all specs
# ---------------------------------------------------------------------------


def check_fused_parity(spec, *, k, chunk, qtile, backend="ref", seed=0,
                       integer=True, **case_kw):
    args, tomb, kw = make_case(spec, seed=seed, integer=integer, **case_kw)
    ov, op = oracle(args, tomb, kw, k)
    fv, fp, fg = ops.segmented_topk(*args, k=k, backend=backend, tomb=tomb,
                                    fused=True, chunk=chunk, qtile=qtile,
                                    **kw)
    tag = f"{spec} k={k} chunk={chunk} qtile={qtile} be={backend}"
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(op),
                                  err_msg=tag + " pos")
    # parity tiers (DESIGN.md §3.9): int8 dequantized distances are
    # allclose-only vs the UNCHUNKED oracle (XLA's reduce vectorization is
    # chunk-shape-dependent at ULP level — the PR 6 note); f32/fp16 on
    # integer data are exact, so the merge order itself is pinned bitwise
    if integer and backend == "ref" and kw["dtype"] != "int8":
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov),
                                      err_msg=tag + " vals")
    else:
        assert np.allclose(np.asarray(fv), np.asarray(ov)), tag + " vals"
    uv, up, ug = ops.segmented_topk(*args, k=k, backend=backend, tomb=tomb,
                                    fused=False, chunk=chunk, **kw)
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(up),
                                  err_msg=tag + " unfused pos")
    np.testing.assert_array_equal(np.asarray(fg), np.asarray(ug),
                                  err_msg=tag + " unfused gid")
    if backend == "ref":
        # ref unfused shares the exact arithmetic: distances bitwise too
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(uv),
                                      err_msg=tag + " unfused vals")


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("k", (1, 4, 17))
def test_lax_fused_bitwise_vs_oracle(spec, k):
    for chunk in (None, 8, 32):
        check_fused_parity(spec, k=k, chunk=chunk, qtile=4)


def test_lax_fused_qtile_sweep():
    """The query-tile decomposition is a pure identity: any qtile gives
    the same bits (per-query results can't see the batch around them)."""
    for qtile in (None, 1, 2, 16):
        check_fused_parity("f32", k=4, chunk=8, qtile=qtile)


def test_fused_handles_empty_and_full_segments():
    args, tomb, kw = make_case("f32", seed=3)
    lens = np.zeros_like(args[7])
    lens[::2] = kw["lmax"]  # alternate empty / span-filling segments
    args = args[:7] + (lens,)
    ov, op = oracle(args, tomb, kw, 4)
    fv, fp, _ = ops.segmented_topk(*args, k=4, backend="ref", tomb=tomb,
                                   fused=True, chunk=8, **kw)
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(op))
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(ov))


if HAVE_HYP:

    @settings(max_examples=15, deadline=None)
    @given(
        spec=st.sampled_from(SPECS),
        k=st.integers(1, 20),
        chunk=st.sampled_from([4, 8, 16, 32]),
        qtile=st.integers(1, 8),
        seed=st.integers(0, 2**16),
        integer=st.booleans(),
    )
    def test_hyp_fused_parity(spec, k, chunk, qtile, seed, integer):
        check_fused_parity(spec, k=k, chunk=chunk, qtile=qtile, seed=seed,
                           integer=integer)


# ---------------------------------------------------------------------------
# 3: the Pallas kernel (interpret mode off-TPU) — small shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", SPECS)
def test_pallas_fused_bitwise_vs_oracle(spec):
    # interpret-mode per-row DMAs are slow: keep shapes tiny
    check_fused_parity(spec, k=4, chunk=8, qtile=2, backend="pallas",
                       N=200, Q=4, lmax=16)


def test_pallas_fused_no_tombstones():
    check_fused_parity("f32", k=4, chunk=8, qtile=2, backend="pallas",
                       N=200, Q=4, lmax=16, tomb=False)


@pytest.mark.parametrize("spec", ("f32", "int8+rerank"))
def test_pallas_fused_segments_ending_at_table_end(spec):
    """A segment that ends at the row table's end with a partial last
    chunk reads its ids from where they lie, not from a window shifted
    back to fit inside the table."""
    check_fused_parity(spec, k=4, chunk=8, qtile=2, backend="pallas",
                       N=200, Q=4, lmax=16, tail=True)
    check_fused_parity(spec, k=4, chunk=8, qtile=2, backend="ref",
                       N=200, Q=4, lmax=16, tail=True)


# ---------------------------------------------------------------------------
# 4: streaming delta scans
# ---------------------------------------------------------------------------


def test_fused_delta_topk_matches_unfused():
    rng = np.random.default_rng(9)
    cap, D, Q, W = 64, 16, 8, 2
    dx = rng.integers(-3, 4, (cap, D)).astype(np.float32)
    dlw = rng.integers(0, 2, (cap, W)).astype(np.int32)
    dxn = np.sum(dx * dx, axis=1).astype(np.float32)
    dtomb = rng.integers(0, 256, ((cap + 7) // 8,)).astype(np.uint8)
    q = rng.integers(-3, 4, (Q, D)).astype(np.float32)
    lq = np.zeros((Q, W), np.int32)
    lq[:, 0] = 1
    for count in (0, 10, cap):
        uv, up = ops.delta_topk(q, lq, jnp.asarray(dx), jnp.asarray(dlw),
                                jnp.asarray(dxn), jnp.asarray(dtomb),
                                count, k=5)
        fv, fp = ops.delta_topk(q, lq, jnp.asarray(dx), jnp.asarray(dlw),
                                jnp.asarray(dxn), jnp.asarray(dtomb),
                                count, k=5, fused=True)
        np.testing.assert_array_equal(np.asarray(fp), np.asarray(up),
                                      err_msg=f"count={count}")
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(uv),
                                      err_msg=f"count={count}")


# ---------------------------------------------------------------------------
# 5: roofline tile model + autotune override
# ---------------------------------------------------------------------------


def test_tile_model_is_deterministic_and_divides():
    for d in (16, 128, 768):
        for lmax in (64, 1024, 8192):
            for dtype in ("f32", "fp16", "int8"):
                for backend in ("ref", "pallas"):
                    a = roofline.fused_scan_tiles(d, lmax, dtype, 64,
                                                  backend=backend)
                    b = roofline.fused_scan_tiles(d, lmax, dtype, 64,
                                                  backend=backend)
                    assert a == b
                    assert lmax % a.rows_per_chunk == 0, (d, lmax, dtype)
                    assert a.rows_per_chunk >= 1
                    assert a.queries_per_tile >= 1
                    assert a.bytes_per_row > 0 and a.intensity > 0
    # pallas tiles must respect the VMEM budget
    t = roofline.fused_scan_tiles(768, 8192, "f32", 64, backend="pallas")
    vmem = (2 * t.queries_per_tile * t.rows_per_chunk
            * roofline.scan_bytes_per_row(768, "f32"))
    assert vmem <= roofline.VMEM_BYTES[roofline.INTERPRET_DEVICE_KIND]


def test_tile_model_rejects_unknown_device_kind():
    """Pallas tiles come from the VMEM the compiler enforces for the chip;
    a device kind with no entry is an error, never a guessed budget."""
    with pytest.raises(ValueError, match="no VMEM entry"):
        roofline.fused_scan_tiles(128, 4096, "f32", 64, backend="pallas",
                                  device_kind="TPU v99")
    # the lax fallback sizes from the host cache and needs no entry
    roofline.fused_scan_tiles(128, 4096, "f32", 64, backend="ref",
                              device_kind="TPU v99")


def test_autotune_override_wins():
    calls = []

    def fake_measure(tc):
        calls.append(tc.rows_per_chunk)
        return 1.0 if tc.rows_per_chunk == 4 else 2.0  # off-model winner

    try:
        best = roofline.autotune_fused_tiles(32, 256, "f32", 16,
                                             backend="ref",
                                             measure=fake_measure)
        assert calls, "autotune never measured"
        if 4 in calls:  # model pick's pow2 neighborhood includes 4
            assert best.rows_per_chunk == 4
        assert best.source == "autotuned"
        after = roofline.fused_scan_tiles(32, 256, "f32", 16, backend="ref")
        assert after == best, "override not consulted by the model"
    finally:  # never leak the pinned override into other tests
        roofline._TILE_OVERRIDES.clear()


def test_resolve_fused_flag():
    assert resolve_fused("auto", backend="pallas") is True
    assert resolve_fused("auto", backend="ref") is False
    assert resolve_fused(True, backend="ref") is True
    assert resolve_fused(False, backend="pallas") is False
    with pytest.raises(ValueError):
        resolve_fused("yes", backend="ref")
    assert clamp_qtile(8, 24) == 8
    assert clamp_qtile(16, 24) == 8
    assert clamp_qtile(4, 6) == 2
    assert clamp_qtile(3, 7) == 1
