"""The plain reference and the comparison that decides ``correct``.

:class:`Reference` is an exact filtered k-NN in float64 on the host, over
the rows of a logical range: squared L2, and a row passes a query iff it
carries every query label.  It is a copy of the brute force of
``repro.launch.smoke.HostReference`` and imports nothing of the program.

:func:`compare` holds the answers of the timed path against it and
returns the numbers that are compared, each with its limit (``LIMITS``):

- ``bad_queries``: queries whose answer has the wrong number of rows, a
  row that is dead, outside the live range or fails the filter, or a
  repeated row.  Exact: limit 0.
- ``rank_gap``: the widest gap, over every query and rank, between the
  float64 distances of the returned rows (sorted) and the reference's
  top-k, as a share of the query's distance scale ``|q|^2 + median
  |x|^2``.  A wrong or missed row shows here.
- ``dist_err``: the widest gap between a distance the program reports and
  the float64 distance of the row it names, as a share of the same scale.
  Computing the scan below float32 shows here.

:func:`control_topk` is the control: the reference put in the program's
place, computed in the nearest precision below float32 that a TPU
offers, ``Precision.HIGH`` (three bfloat16 passes: every product
``a*b`` taken as ``ah*bh + ah*bl + al*bh`` with ``a = ah + al`` split into
bfloat16 parts, the ``al*bl`` term dropped, sums in float32).  It is
emulated explicitly, so it reads the same on the chip and on the CPU.
"""
from __future__ import annotations

import numpy as np

# Each limit lies between the largest reading of sound runs and the
# smallest reading of the control or of a planted fault; PERF.md gives the
# readings each was set from.
LIMITS = {"bad_queries": 0, "rank_gap": 2e-6, "dist_err": 1e-6}


class Reference:
    """Exact filtered top-k over logical rows ``[lo, hi)`` of ``x``."""

    def __init__(self, x: np.ndarray, member: np.ndarray):
        self.x = np.asarray(x, np.float64)
        self.member = np.asarray(member, bool)
        self.xn = np.einsum("ij,ij->i", self.x, self.x)
        self.xn_median = float(np.median(self.xn))

    def passes(self, qmask: np.ndarray, lo: int, hi: int) -> np.ndarray:
        """[hi - lo] bool: live rows of the range carrying every label."""
        return self.member[lo:hi][:, qmask].all(axis=1)

    def dist(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        q = np.asarray(q, np.float64)
        return self.xn[ids] - 2.0 * (self.x[ids] @ q) + q @ q

    def scale(self, q: np.ndarray) -> float:
        q = np.asarray(q, np.float64)
        return float(q @ q) + self.xn_median

    def topk(self, qv: np.ndarray, qmasks: np.ndarray, k: int, lo: int,
             hi: int, block: int = 16):
        """(ids [Q, k] logical, -1 where fewer than k rows pass; float64
        distances [Q, k], +inf there), ascending."""
        q64 = np.asarray(qv, np.float64)
        ids = np.full((len(q64), k), -1, np.int64)
        dist = np.full((len(q64), k), np.inf)
        xs, xn = self.x[lo:hi], self.xn[lo:hi]
        for b in range(0, len(q64), block):
            qb = q64[b:b + block]
            d = xn[:, None] - 2.0 * (xs @ qb.T) + np.einsum(
                "ij,ij->i", qb, qb)[None, :]
            for j in range(qb.shape[0]):
                dj = np.where(self.passes(qmasks[b + j], lo, hi), d[:, j],
                              np.inf)
                m = min(k, int(np.isfinite(dj).sum()))
                if m == 0:
                    continue
                top = np.argpartition(dj, m - 1)[:m]
                top = top[np.argsort(dj[top], kind="stable")]
                ids[b + j, :m] = top + lo
                dist[b + j, :m] = dj[top]
        return ids, dist


def compare(ref: Reference, qv, qmasks, got_ids, got_d, lo: int, hi: int,
            want_ids, want_d) -> dict:
    """The compared numbers (module docstring) for one set of answers.
    ``got_ids`` are logical ids, -1 for an empty slot or an id the run
    could not place; ``got_d`` the distances the program reported."""
    bad = 0
    rank_gap = dist_err = 0.0
    for i in range(len(qv)):
        m = int((want_ids[i] >= 0).sum())
        gi = got_ids[i]
        got = gi[gi >= 0]
        ok = (got.size == m and (gi[:m] >= 0).all()
              and np.unique(got).size == got.size
              and ((got >= lo) & (got < hi)).all())
        if ok and m:
            ok = bool(ref.member[got][:, qmasks[i]].all())
        if not ok:
            bad += 1
            continue
        if m == 0:
            continue
        scale = ref.scale(qv[i])
        d64 = ref.dist(qv[i], got)
        rank_gap = max(rank_gap, float(
            np.max(np.abs(np.sort(d64) - want_d[i, :m]))) / scale)
        dist_err = max(dist_err, float(
            np.max(np.abs(np.asarray(got_d[i, :m], np.float64) - d64)))
            / scale)
    return {"bad_queries": bad, "rank_gap": rank_gap, "dist_err": dist_err}


def merge(parts: list[dict]) -> dict:
    """The worst of several :func:`compare` readings."""
    out = {"bad_queries": 0, "rank_gap": 0.0, "dist_err": 0.0}
    for p in parts:
        out["bad_queries"] += p["bad_queries"]
        out["rank_gap"] = max(out["rank_gap"], p["rank_gap"])
        out["dist_err"] = max(out["dist_err"], p["dist_err"])
    return out


def within(numbers: dict) -> bool:
    return all(numbers[name] <= limit for name, limit in LIMITS.items())


def altered_topk(x, member, qv, qmasks, k, lo, hi):
    """A planted fault for the limits' readings: the reference's answer
    with its k-th row replaced by the (k+1)-th nearest passing row, where
    there is one, and the distance of the row it now names."""
    ref = Reference(x, member)
    ids, dist = ref.topk(qv, qmasks, k + 1, lo, hi)
    out_i, out_d = ids[:, :k].copy(), dist[:, :k].copy()
    swap = ids[:, k] >= 0
    out_i[swap, k - 1] = ids[swap, k]
    out_d[swap, k - 1] = dist[swap, k]
    return out_i, out_d


def _split(a):
    import jax.numpy as jnp
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _dot3(a, b):
    """``a @ b`` at three bfloat16 passes (``Precision.HIGH``)."""
    import jax
    import jax.numpy as jnp
    ah, al = _split(a)
    bh, bl = _split(b)
    hp = jax.lax.Precision.HIGHEST   # products of bf16 parts are exact
    return (jnp.matmul(ah, bh, precision=hp)
            + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


def control_topk(x: np.ndarray, member: np.ndarray, qv: np.ndarray,
                 qmasks: np.ndarray, k: int, lo: int, hi: int,
                 block: int = 64):
    """The control (module docstring): (ids [Q, k] logical with -1 where
    empty, float32 distances [Q, k]) of the reference at three bfloat16
    passes, on the default JAX device."""
    import jax
    import jax.numpy as jnp
    xs = jnp.asarray(np.asarray(x[lo:hi], np.float32))
    xn = _norms3(xs)
    code = jnp.asarray(_codes(member[lo:hi]))
    ids = np.full((len(qv), k), -1, np.int64)
    dist = np.full((len(qv), k), np.inf, np.float32)
    for b in range(0, len(qv), block):
        q = jnp.asarray(np.asarray(qv[b:b + block], np.float32))
        qc = jnp.asarray(_codes(qmasks[b:b + block]))
        qn = _norms3(q)
        d = qn[:, None] - 2.0 * _dot3(q, xs.T) + xn[None, :]
        keep = (code[None, :] & qc[:, None]) == qc[:, None]
        d = jnp.where(keep, d, jnp.inf)
        neg, top = jax.lax.top_k(-d, min(k, hi - lo))
        vals = np.asarray(-neg)
        top = np.asarray(top)
        for j in range(vals.shape[0]):
            m = int(np.isfinite(vals[j]).sum())
            ids[b + j, :m] = top[j, :m] + lo
            dist[b + j, :m] = vals[j, :m]
    return ids, dist


def _codes(member: np.ndarray) -> np.ndarray:
    """Label membership rows as int32 bit codes (at most 31 labels)."""
    if member.shape[1] > 31:
        raise ValueError("the control packs at most 31 labels in a word")
    return (member.astype(np.int32)
            @ (1 << np.arange(member.shape[1], dtype=np.int32)))


def _norms3(a):
    """Row sums of squares at three bfloat16 passes."""
    import jax.numpy as jnp
    ah, al = _split(a)
    return jnp.sum(ah * ah + 2.0 * ah * al, axis=1)
