"""IVFIndex — inverted-file backend (k-means coarse quantizer + cluster scan).

Demonstrates the paper's index-flexibility claim on a second index family.
Build: JAX Lloyd iterations (jit'd); rows are re-ordered cluster-major so a
probe scans a contiguous range.  Search implements the paper's incremental
PostFiltering semantics: probe the ``nprobe`` nearest clusters, and if fewer
than k rows pass the label filter, double the probe set and continue — the
k+1 expansion of Lemma 3.2 at cluster granularity.

Search is one jit-cached program per (k, bucket) — the ``search_padded``
contract of ``index.base``.  The probe-doubling loop is de-sequentialized
into **static wave boundaries** (cumulative probe counts ``nprobe, 3·nprobe,
7·nprobe, …`` clamped at the cluster count): per-query passing counts at
every boundary are computed in one masked-distance pass, the stopping
boundary selected with an argmax, and rows outside the probed prefix masked
to +inf.  The oracle's stable (probe-order, storage-order) tie-break is
preserved by scattering each query's rows into probe order — the
permutation is pure cluster-major layout arithmetic (probe-prefix start of
the row's cluster + offset within it), no [Q, N] sort — before
``lax.top_k`` (XLA TopK breaks value ties toward the lower index).  The
distance+filter pass is the same arithmetic as ``kernels/masked_distance``
(via its jnp oracle ``kernels.ref.masked_distance``), so on TPU the pass
lowers onto the same fused MXU/VPU tiles as the flat backend.

Cost profile: the traced program is a *dense* masked pass over all N rows
— probe waves gate which rows may appear in the result (the paper's
incremental semantics, verified bit-exactly against the sequential probe
loop in ``tests/test_search_padded_parity.py``) but do not skip their
distance FLOPs.  That trade is deliberate for the accelerator target:
one MXU-shaped [bucket, N] matmul beats per-query ragged list gathers at
sub-index scale, and keeps the program shape static per (k, bucket).
Gather-based probed-list sparsity (capped [bucket, P·Lmax] gathers) is
the recorded follow-up for very large sub-indexes.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..kernels import ref
from .base import bucket_cache, pad_to_bucket, register_index


def _mm(a, b):
    """f32 matmul at full precision (a TPU's default is one bf16 pass)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("n_clusters", "iters"))
def _kmeans(x: jnp.ndarray, n_clusters: int, iters: int, seed: int = 0):
    n, d = x.shape
    key = jax.random.PRNGKey(seed)
    init = jax.random.choice(key, n, (n_clusters,), replace=False)
    cents = x[init]

    def step(cents, _):
        d2 = (jnp.sum(x * x, 1, keepdims=True) - 2 * _mm(x, cents.T)
              + jnp.sum(cents * cents, 1)[None, :])
        assign = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(assign, n_clusters, dtype=x.dtype)
        sums = _mm(one_hot.T, x)
        counts = jnp.maximum(one_hot.sum(0)[:, None], 1.0)
        new = sums / counts
        # keep empty clusters where they were
        new = jnp.where(one_hot.sum(0)[:, None] > 0, new, cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    d2 = (jnp.sum(x * x, 1, keepdims=True) - 2 * _mm(x, cents.T)
          + jnp.sum(cents * cents, 1)[None, :])
    return cents, jnp.argmin(d2, axis=1)


def _wave_boundaries(n_clusters: int, nprobe: int) -> tuple[int, ...]:
    """Cumulative probed-cluster counts after each doubling wave, clamped at
    the cluster count: ``nprobe, 3·nprobe, 7·nprobe, …, n_clusters``."""
    bounds: list[int] = []
    probed, wave = 0, max(nprobe, 1)
    while probed < n_clusters:
        probed = min(probed + wave, n_clusters)
        bounds.append(probed)
        wave *= 2
    return tuple(bounds)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "boundaries"))
def _ivf_padded_topk(q, lq, xb, lxw, cents, row_cluster, row_in_cluster,
                     cluster_sizes, row_map, tomb=None, *,
                     k: int, metric: str, boundaries: tuple[int, ...]):
    """Batched incremental-probe IVF search, fully static shapes.

    q [Q, D] f32; lq [Q, W] i32; xb [N, D] cluster-major rows; lxw [N, W];
    cents [C, D]; row_cluster [N] i32 (cluster id per stored row);
    row_in_cluster [N] i32 (offset within the row's cluster);
    cluster_sizes [C] i32; row_map [N] i32 (stored row -> original local
    id).  Returns (vals [Q, k] asc, ids [Q, k] original-local; id == N ⇒
    empty slot).

    ``tomb`` (optional packed bitmap over ORIGINAL local row ids — the id
    space this search returns; ``index.base`` contract): the per-row
    tombstone byte is gathered through ``row_map`` and AND-ed into the
    pass mask BEFORE the wave-boundary continuation counts, so the
    Lemma 3.2 probe doubling widens over deleted rows exactly as it does
    over filtered-out ones — a fully-tombstoned probe wave accumulates
    zero passing rows and the loop keeps doubling until k live passing
    rows are found or every cluster is probed (guaranteed termination at
    ``boundaries[-1]``).  ``tomb=None`` traces the exact tombstone-free
    program.
    """
    N = xb.shape[0]

    # 1. probe order: stable argsort over centroid distances (ties toward
    #    the lower centroid id), inverted to a per-cluster probe rank
    cd = ref.distances(q, cents, metric)                       # [Q, C]
    order_c = jnp.argsort(cd, axis=1, stable=True)             # [Q, C]
    rank_c = jnp.argsort(order_c, axis=1, stable=True)         # inverse perm

    # 2. fused distance + label filter over ALL rows (one masked pass);
    #    the tombstone AND composes with the containment filter — a
    #    deleted row simply stops passing, no distance value changes
    d = ref.masked_distance(q, xb, lq, lxw, metric)            # [Q, N]
    passing = jnp.isfinite(d)
    if tomb is not None:
        passing = passing & ref.tombstone_mask(tomb, row_map)[None, :]

    # 3. Lemma 3.2 probe continuation: per-cluster passing counts, summed
    #    over the probe-order prefix at each static wave boundary; the
    #    probed prefix P is the first boundary accumulating >= k passing
    #    rows (else every cluster — the incremental loop exhausted)
    onehot = jax.nn.one_hot(row_cluster, cents.shape[0], dtype=jnp.float32)
    cnt = passing.astype(jnp.float32) @ onehot                 # [Q, C]
    cum = jnp.cumsum(jnp.take_along_axis(cnt, order_c, axis=1), axis=1)
    bnds = jnp.asarray(boundaries, dtype=jnp.int32)            # [B]
    totals = cum[:, bnds - 1]                                  # [Q, B]
    met = totals >= k
    first = jnp.argmax(met, axis=1)                            # 0 if none met
    P = jnp.where(jnp.any(met, axis=1), bnds[first], bnds[-1])  # [Q]

    # 4. keep rows whose cluster lands in the probed prefix
    row_rank = jnp.take_along_axis(
        rank_c, jnp.broadcast_to(row_cluster[None, :], d.shape), axis=1)
    d = jnp.where(passing & (row_rank < P[:, None]), d, jnp.inf)

    # 5. scatter rows into probe order so lax.top_k's lower-index
    #    tie-break reproduces the incremental scan's stable (probe-order,
    #    storage-order) ordering exactly.  The position of a row is pure
    #    layout arithmetic — probe-prefix start of its cluster plus its
    #    offset within the cluster — so no [Q, N] sort is needed
    sz_sorted = jnp.take_along_axis(
        jnp.broadcast_to(cluster_sizes[None, :], rank_c.shape),
        order_c, axis=1)                                        # [Q, C]
    start_sorted = jnp.cumsum(sz_sorted, axis=1) - sz_sorted    # exclusive
    pos = (jnp.take_along_axis(start_sorted, row_rank, axis=1)
           + row_in_cluster[None, :])                           # [Q, N] perm
    qi = jnp.arange(q.shape[0])[:, None]
    dp = jnp.zeros_like(d).at[qi, pos].set(d)
    perm = jnp.zeros(d.shape, jnp.int32).at[qi, pos].set(
        jnp.arange(N, dtype=jnp.int32))
    if k > N:   # fewer rows than requested: pad the candidate matrix
        dp = jnp.pad(dp, ((0, 0), (0, k - N)), constant_values=jnp.inf)
        perm = jnp.pad(perm, ((0, 0), (0, k - N)))
    neg, pos_k = jax.lax.top_k(-dp, k)
    vals = -neg
    stored = jnp.take_along_axis(perm, pos_k, axis=1)
    ids = jnp.where(jnp.isinf(vals), N,
                    row_map[jnp.clip(stored, 0, N - 1)])
    vals = jnp.where(jnp.isinf(vals), jnp.float32(jnp.inf), vals)
    return vals, ids.astype(jnp.int32)


@register_index("ivf")
class IVFIndex:
    supports_tombstones = True   # lazy-delete capability (index.base)

    def __init__(self, vectors: np.ndarray, label_words: np.ndarray,
                 metric: str = "l2", n_clusters: int | None = None,
                 nprobe: int = 8, kmeans_iters: int = 8, seed: int = 0):
        n, d = vectors.shape
        self.metric = metric
        self.num_vectors, self.dim = n, d
        self.nprobe = nprobe
        # clamp: a tiny selected sub-index cannot host more clusters than
        # vectors (ELI builds indexes for label groups of any size)
        c = n_clusters or max(1, min(int(np.sqrt(n)), n))
        c = max(1, min(c, n))
        x = jnp.asarray(vectors, dtype=jnp.float32)
        cents, assign = _kmeans(x, c, kmeans_iters, seed)
        assign = np.asarray(assign)
        order = np.argsort(assign, kind="stable")
        self.centroids = np.asarray(cents, dtype=np.float32)
        self.vectors = np.ascontiguousarray(vectors[order], dtype=np.float32)
        self.label_words = np.ascontiguousarray(label_words[order],
                                                dtype=np.int32)
        self.row_map = order.astype(np.int32)   # reordered -> original local id
        counts = np.bincount(assign, minlength=c)
        self.offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self.n_clusters = c
        self._boundaries = _wave_boundaries(c, nprobe)
        # device-resident copies for the jit'd search program
        self._xb = jnp.asarray(self.vectors)
        self._lxw = jnp.asarray(self.label_words)
        self._cents = jnp.asarray(self.centroids)
        row_cluster = np.repeat(np.arange(c, dtype=np.int32), counts)
        self._row_cluster = jnp.asarray(row_cluster)
        self._row_in_cluster = jnp.asarray(
            (np.arange(n) - self.offsets[row_cluster]).astype(np.int32))
        self._cluster_sizes = jnp.asarray(counts.astype(np.int32))
        self._row_map_dev = jnp.asarray(self.row_map)

    @classmethod
    def build(cls, vectors, label_words, metric: str = "l2", **params):
        return cls(vectors, label_words, metric, **params)

    def search(self, queries: np.ndarray, query_label_words: np.ndarray,
               k: int, tomb=None) -> tuple[np.ndarray, np.ndarray]:
        # pad to the executor's power-of-two bucket convention so direct
        # callers with jittery batch sizes reuse the same traced programs
        # instead of compiling one per distinct Q (shape stability)
        return pad_to_bucket(self.search_padded, queries,
                             query_label_words, k, self.num_vectors,
                             tomb=tomb)

    def search_padded(self, queries: np.ndarray,
                      query_label_words: np.ndarray,
                      k: int, tomb=None) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Bucket-shaped incremental-probe search (``index.base`` contract).

        One traced program per (index, k, bucket); the module-level jit
        shares XLA executables across indexes with coinciding shapes,
        metric, and wave schedule.  ``tomb`` (packed bitmap over local
        rows) is a traced argument — delete batches never retrace; the
        tombstone-free ``None`` variant keeps its own static trace.
        """
        cache = bucket_cache(self)
        bucket = queries.shape[0]
        fn = cache.get((k, bucket))
        if fn is None:
            def fn(q, lq, tomb=None, _k=k):
                return _ivf_padded_topk(q, lq, self._xb, self._lxw,
                                        self._cents, self._row_cluster,
                                        self._row_in_cluster,
                                        self._cluster_sizes,
                                        self._row_map_dev, tomb, k=_k,
                                        metric=self.metric,
                                        boundaries=self._boundaries)
            cache[(k, bucket)] = fn
        q = jnp.asarray(queries, dtype=jnp.float32)
        lq = jnp.asarray(query_label_words, dtype=jnp.int32)
        tomb = None if tomb is None else jnp.asarray(tomb, jnp.uint8)
        return fn(q, lq, tomb)

    @property
    def nbytes(self) -> int:
        return (self.vectors.nbytes + self.centroids.nbytes
                + self.label_words.nbytes + self.offsets.nbytes)
