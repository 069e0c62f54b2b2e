"""GraphIndex — degree-bounded proximity graph (fidelity backend).

The paper's experiments use HNSW; its selection scheme only requires *some*
top-k index with incremental (k+1) search.  This backend preserves the
paper's graph cost model (node degree bounded by a constant M, so index
cost ∝ #vectors — paper §3.2 Remark) in a TPU-expressible form:

  * adjacency is a dense ``[N, M]`` int32 array (no pointers, -1 = pad) —
    gatherable on device;
  * beam search is a ``jax.lax.while_loop`` over fixed-shape pools, vmapped
    over the query batch; the per-hop neighbor gather + distance is the
    access pattern the ``gather_distance`` Pallas kernel implements
    (scalar-prefetch DMA); the batched search here uses the same arithmetic
    via jnp gather so the whole batch jits as one program.

Construction is Vamana-style: exact top-C candidate lists (blockwise
matmul — MXU-shaped work), α-robust prune, reverse-edge insertion, medoid
connectivity fix-up.  On CPU this is vectorized numpy; the arithmetic is
identical to what the flat-scan kernel computes per tile on TPU.

Both PostFiltering and PreFiltering strategies (paper §2.2) are supported;
hop/distance-computation counters are returned so benchmarks can validate
the Lemma 3.2 cost model (expected extra hops ≈ k/c).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..kernels import ref
from .base import bucket_cache, register_index

INF = float("inf")


# ---------------------------------------------------------------------------
# Construction (host-side, vectorized)
# ---------------------------------------------------------------------------

def _pairwise_block_topk(x: np.ndarray, n_cand: int, block: int = 2048) -> np.ndarray:
    """Exact top-``n_cand`` neighbor ids per row (excluding self), blockwise."""
    n = x.shape[0]
    sq = np.sum(x * x, axis=1)
    out = np.empty((n, min(n_cand, n - 1)), dtype=np.int32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        d = sq[lo:hi, None] - 2.0 * (x[lo:hi] @ x.T) + sq[None, :]
        rows = np.arange(lo, hi)
        d[np.arange(hi - lo), rows] = INF           # exclude self
        k = out.shape[1]
        part = np.argpartition(d, k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d, part, axis=1)
        order = np.argsort(pd, axis=1, kind="stable")
        out[lo:hi] = np.take_along_axis(part, order, axis=1).astype(np.int32)
    return out


def _robust_prune(x: np.ndarray, i: int, cand: np.ndarray, alpha: float,
                  M: int) -> np.ndarray:
    """Vamana α-RNG prune: keep candidates not α-dominated by a kept one."""
    cand = cand[cand != i]
    if cand.size == 0:
        return cand.astype(np.int32)
    _, first = np.unique(cand, return_index=True)
    cand = cand[np.sort(first)]
    d_i = np.sum((x[cand] - x[i]) ** 2, axis=1)
    order = np.argsort(d_i, kind="stable")
    cand, d_i = cand[order], d_i[order]
    kept: list[int] = []
    alive = np.ones(cand.size, dtype=bool)
    for j in range(cand.size):
        if not alive[j]:
            continue
        kept.append(j)
        if len(kept) == M:
            break
        # occlude: drop c with α·d(kept_j, c) ≤ d(i, c)
        d_jc = np.sum((x[cand] - x[cand[j]]) ** 2, axis=1)
        alive &= ~(alpha * d_jc <= d_i)
        alive[j] = False
    return cand[kept].astype(np.int32)


def build_vamana(x: np.ndarray, M: int = 16, n_cand: int = 64,
                 alpha: float = 1.2, seed: int = 0) -> tuple[np.ndarray, int]:
    """Build a degree-≤M navigable graph.  Returns (adj [N, M] int32, medoid)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    n = x.shape[0]
    if n == 1:
        return np.full((1, M), -1, dtype=np.int32), 0
    medoid = int(np.argmin(np.sum((x - x.mean(0)) ** 2, axis=1)))
    cands = _pairwise_block_topk(x, n_cand)

    adj = np.full((n, M), -1, dtype=np.int32)
    deg = np.zeros(n, dtype=np.int32)
    for i in range(n):
        kept = _robust_prune(x, i, cands[i], alpha, M)
        adj[i, : kept.size] = kept
        deg[i] = kept.size

    # reverse edges (keeps the graph navigable from sparse regions)
    for i in range(n):
        for j in adj[i, : deg[i]]:
            if i in adj[j, : deg[j]]:
                continue
            if deg[j] < M:
                adj[j, deg[j]] = i
                deg[j] += 1
            else:
                kept = _robust_prune(x, j, np.append(adj[j, : deg[j]], i), alpha, M)
                adj[j, :] = -1
                adj[j, : kept.size] = kept
                deg[j] = kept.size

    # connectivity fix-up: any node with zero in-degree gets an edge from medoid
    indeg = np.zeros(n, dtype=np.int64)
    flat = adj[adj >= 0]
    np.add.at(indeg, flat, 1)
    orphans = np.where((indeg == 0) & (np.arange(n) != medoid))[0]
    for o in orphans:
        slot = deg[medoid] % M
        adj[medoid, slot] = o
        deg[medoid] = min(deg[medoid] + 1, M)
    return adj, medoid


# ---------------------------------------------------------------------------
# Search (JAX, batched)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SearchStats:
    hops: np.ndarray        # [Q] int32 — nodes expanded
    dist_comps: np.ndarray  # [Q] int32 — distance computations


def _contains_words(lq: jnp.ndarray, lx: jnp.ndarray) -> jnp.ndarray:
    """lq [W] vs lx [..., W] -> [...] bool containment."""
    return jnp.all((lq & lx) == lq, axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "ef", "strategy", "max_steps",
                                             "metric"))
def _beam_search_batch(adj, xb, lxw, q, lq, entries, tomb=None, *, k: int,
                       ef: int, strategy: str = "post", max_steps: int = 512,
                       metric: str = "l2"):
    """Batched filtered beam search.

    adj [N, M] int32 (-1 pad); xb [N, D] f32; lxw [N, W] int32;
    q [Q, D] f32; lq [Q, W] int32; entries [Q, E] int32 (-1 pad).
    Returns (dists [Q, k], ids [Q, k] — id N ⇒ empty, hops [Q], dcomps [Q]).

    ``tomb`` (optional packed bitmap over node ids; ``index.base``
    contract): tombstoned nodes are excluded from the RESULT pool via a
    gathered-byte AND on the passing mask, but stay fully navigable — the
    candidate pool, visited set, and (under the "pre" strategy) the
    label-passing navigation mask ignore tombstones, mirroring the arena
    path's walk-but-don't-return semantics (DESIGN.md §3.6): deleting a
    bridge node must not disconnect live rows behind it.  ``tomb=None``
    traces the exact tombstone-free program.
    """
    N, M = adj.shape
    xb_sq = jnp.sum(xb * xb, axis=1)

    def alive_mask(ids):
        if tomb is None:
            return jnp.ones(ids.shape, dtype=bool)
        return ref.tombstone_mask(tomb, jnp.clip(ids, 0, N - 1))

    def dist_to(qr, ids):
        rows = xb[jnp.clip(ids, 0, N - 1)]
        ip = jnp.matmul(rows, qr, precision=jax.lax.Precision.HIGHEST)
        if metric == "ip":
            return -ip
        return xb_sq[jnp.clip(ids, 0, N - 1)] - 2.0 * ip + jnp.sum(qr * qr)

    def one(qr, lqr, ent):
        valid_e = ent >= 0
        e_ids = jnp.where(valid_e, ent, 0)
        e_d = jnp.where(valid_e, dist_to(qr, e_ids), INF)
        e_pass = _contains_words(lqr, lxw[e_ids]) & valid_e & alive_mask(e_ids)

        visited = jnp.zeros(N + 1, dtype=bool)
        visited = visited.at[jnp.where(valid_e, ent, N)].set(True)

        # candidate pool (navigation) — seeds always navigable
        E = ent.shape[0]
        pool_d = jnp.concatenate([e_d, jnp.full(ef, INF)])
        pool_i = jnp.concatenate([jnp.where(valid_e, ent, N),
                                  jnp.full(ef, N, dtype=jnp.int32)])
        pool_x = jnp.concatenate([~valid_e, jnp.ones(ef, dtype=bool)])  # expanded
        order = jnp.argsort(pool_d, stable=True)[:ef]
        pool_d, pool_i, pool_x = pool_d[order], pool_i[order], pool_x[order]

        # result pool (passing nodes only) — ef-sized, HNSW semantics: the
        # search explores until no unexpanded candidate can beat the ef-th
        # accumulated passing result; top-k is sliced off at the end.
        res_d = jnp.full(ef, INF)
        res_i = jnp.full(ef, N, dtype=jnp.int32)
        rd0 = jnp.where(e_pass, e_d, INF)
        cat_d = jnp.concatenate([res_d, rd0])
        cat_i = jnp.concatenate([res_i, jnp.where(e_pass, ent, N)])
        order = jnp.argsort(cat_d, stable=True)[:ef]
        res_d, res_i = cat_d[order], cat_i[order]

        def cond(state):
            pool_d, pool_i, pool_x, visited, res_d, res_i, hops, dc = state
            un_d = jnp.where(pool_x, INF, pool_d)
            best = jnp.min(un_d)
            # continue while an unexpanded candidate could still improve the
            # k-th result (res_d[-1] = inf while results are not yet full)
            return (hops < max_steps) & jnp.isfinite(best) & (best <= res_d[-1])

        def body(state):
            pool_d, pool_i, pool_x, visited, res_d, res_i, hops, dc = state
            un_d = jnp.where(pool_x, INF, pool_d)
            slot = jnp.argmin(un_d)
            u = pool_i[slot]
            pool_x = pool_x.at[slot].set(True)

            nbrs = adj[jnp.clip(u, 0, N - 1)]                       # [M]
            nv = (nbrs >= 0) & ~visited[jnp.clip(nbrs, 0, N - 1)]
            safe = jnp.where(nv, nbrs, N)
            visited = visited.at[safe].set(True)
            nd = jnp.where(nv, dist_to(qr, jnp.where(nv, nbrs, 0)), INF)
            npass = _contains_words(lqr, lxw[jnp.clip(nbrs, 0, N - 1)]) & nv
            # result inclusion additionally requires liveness; navigation
            # (below) deliberately does NOT — tombstoned nodes keep the
            # graph connected exactly as before their deletion
            nres = npass & alive_mask(nbrs)

            nav = npass if strategy == "pre" else nv
            cat_d = jnp.concatenate([pool_d, jnp.where(nav, nd, INF)])
            cat_i = jnp.concatenate([pool_i, safe])
            cat_x = jnp.concatenate([pool_x, jnp.zeros(M, dtype=bool)])
            order = jnp.argsort(cat_d, stable=True)[:ef]
            pool_d, pool_i, pool_x = cat_d[order], cat_i[order], cat_x[order]

            cat_d = jnp.concatenate([res_d, jnp.where(nres, nd, INF)])
            cat_i = jnp.concatenate([res_i, jnp.where(nres, nbrs, N)])
            order = jnp.argsort(cat_d, stable=True)[:ef]
            res_d, res_i = cat_d[order], cat_i[order]
            return (pool_d, pool_i, pool_x, visited, res_d, res_i,
                    hops + 1, dc + jnp.sum(nv, dtype=jnp.int32))

        state = (pool_d, pool_i, pool_x, visited, res_d, res_i,
                 jnp.int32(0), jnp.sum(valid_e, dtype=jnp.int32))
        state = jax.lax.while_loop(cond, body, state)
        _, _, _, _, res_d, res_i, hops, dc = state
        return res_d[:k], res_i[:k], hops, dc

    return jax.vmap(one)(q, lq, entries)


@register_index("graph")
class GraphIndex:
    """Degree-bounded proximity graph with filtered beam search."""

    supports_tombstones = True   # lazy-delete capability (index.base)

    def __init__(self, vectors: np.ndarray, label_words: np.ndarray,
                 metric: str = "l2", M: int = 16, n_cand: int = 64,
                 alpha: float = 1.2, ef_search: int = 64,
                 strategy: str = "post", seed: int = 0,
                 adjacency: np.ndarray | None = None,
                 medoid: int | None = None):
        self.vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        self.label_words = np.ascontiguousarray(label_words, dtype=np.int32)
        self.metric = metric
        self.num_vectors, self.dim = self.vectors.shape
        self.M = M
        self.ef_search = ef_search
        self.strategy = strategy
        if adjacency is None:
            adjacency, medoid = build_vamana(self.vectors, M=M, n_cand=n_cand,
                                             alpha=alpha, seed=seed)
        self.adjacency = adjacency
        self.medoid = int(medoid if medoid is not None else 0)
        self.last_stats: SearchStats | None = None
        # device-resident copies shared by every traced search program
        self._adj_dev = jnp.asarray(self.adjacency)
        self._xb_dev = jnp.asarray(self.vectors)
        self._lxw_dev = jnp.asarray(self.label_words)

    @classmethod
    def build(cls, vectors, label_words, metric: str = "l2", **params):
        return cls(vectors, label_words, metric, **params)

    def default_entries(self, n_queries: int) -> np.ndarray:
        return np.full((n_queries, 1), self.medoid, dtype=np.int32)

    def _max_steps(self) -> int:
        return 4 * self.num_vectors // max(self.M, 1) + 64

    def search(self, queries: np.ndarray, query_label_words: np.ndarray,
               k: int, ef: int | None = None, entries: np.ndarray | None = None,
               strategy: str | None = None,
               tomb=None) -> tuple[np.ndarray, np.ndarray]:
        # bucket the batch to the executor's power-of-two convention so
        # direct callers reuse traced programs across jittery batch sizes;
        # pad lanes get entry -1 (no valid seed), which fails the loop
        # condition on the first check — zero wasted hops
        q = np.asarray(queries, dtype=np.float32)
        lw = np.asarray(query_label_words, dtype=np.int32)
        g = q.shape[0]
        if g == 0:
            empty = np.zeros(0, np.int32)
            self.last_stats = SearchStats(hops=empty, dist_comps=empty)
            return (np.full((0, k), np.inf, np.float32),
                    np.full((0, k), self.num_vectors, np.int32))
        bucket = 1 << (g - 1).bit_length()
        qp = np.zeros((bucket, q.shape[1]), np.float32)
        qp[:g] = q
        lp = np.zeros((bucket, lw.shape[1]), np.int32)
        lp[:g] = lw
        if entries is None:
            entries = self.default_entries(g)
        ent = np.full((bucket, entries.shape[1]), -1, np.int32)
        ent[:g] = entries
        ef = max(ef or self.ef_search, k)
        tomb = None if tomb is None else jnp.asarray(tomb, jnp.uint8)
        d, i, hops, dc = _beam_search_batch(
            self._adj_dev, self._xb_dev, self._lxw_dev,
            jnp.asarray(qp), jnp.asarray(lp), jnp.asarray(ent), tomb,
            k=k, ef=ef, strategy=strategy or self.strategy,
            max_steps=self._max_steps(), metric=self.metric)
        self.last_stats = SearchStats(hops=np.asarray(hops)[:g],
                                      dist_comps=np.asarray(dc)[:g])
        return np.asarray(d)[:g], np.asarray(i)[:g]

    def search_padded(self, queries: np.ndarray,
                      query_label_words: np.ndarray,
                      k: int, ef: int | None = None,
                      strategy: str | None = None,
                      tomb=None) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Bucket-shaped beam search (``index.base`` contract).

        The beam loop is already a fixed-shape ``lax.while_loop`` vmapped
        over the batch (a vmapped while_loop freezes finished lanes via
        select, so each lane's result is independent of its batch
        neighbors — pad rows cannot perturb real rows); bucketing the batch
        axis makes it trace once per (index, k, bucket[, ef, strategy]).
        ``tomb`` (packed bitmap over node ids) is a traced argument — see
        ``_beam_search_batch`` for the walk-but-don't-return semantics.
        """
        cache = bucket_cache(self)
        bucket = queries.shape[0]
        ef = max(ef or self.ef_search, k)
        strategy = strategy or self.strategy
        fn = cache.get((k, bucket, ef, strategy))
        if fn is None:
            def fn(q, lq, tomb=None, _k=k, _ef=ef, _s=strategy):
                entries = jnp.full((q.shape[0], 1), self.medoid, jnp.int32)
                d, i, _, _ = _beam_search_batch(
                    self._adj_dev, self._xb_dev, self._lxw_dev, q, lq,
                    entries, tomb, k=_k, ef=_ef, strategy=_s,
                    max_steps=self._max_steps(), metric=self.metric)
                return d, i
            cache[(k, bucket, ef, strategy)] = fn
        q = jnp.asarray(queries, dtype=jnp.float32)
        lq = jnp.asarray(query_label_words, dtype=jnp.int32)
        tomb = None if tomb is None else jnp.asarray(tomb, jnp.uint8)
        return fn(q, lq, tomb)

    @property
    def nbytes(self) -> int:
        return (self.vectors.nbytes + self.label_words.nbytes
                + self.adjacency.nbytes)
