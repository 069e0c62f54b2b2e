"""Inputs of a run, made from ``--seed`` and the cell's configuration.

The label generator is the paper's (UNG's): each row carries a
Poisson(mean) number of labels, clipped to ``[0, min(max, L)]``, drawn
without replacement with Zipf(a) popularity.  It is a vectorised copy of
``repro.core.labels.generate_label_sets`` with the same distribution: the
per-row successive weighted draw is replaced by the Gumbel-top-k trick,
which gives the same law for the drawn set (Efraimidis and Spirakis).

The *multiset* of row label sets is a fixed draw (``label_seed`` in the
configuration); ``--seed`` permutes which row carries which set and draws
every vector and every call's query label sets.  The multiset is fixed
because it fixes the selection and the length of the program's row table,
a shape of every compiled scan: a multiset drawn from ``--seed`` would
make each new seed compile every program afresh in set-up.

Past the base rows the run's rows go on as a stream (:meth:`Dataset.rows`):
row ``r >= n_rows`` has a vector and a label set that are a pure function
of ``(seed, r)``, drawn in blocks of :data:`BLOCK` rows, so that any range
regenerates without anyone keeping copies.  Its label set is a fresh draw
of the paper's generator at the configuration's Zipf and Poisson settings,
from the seed and not from the fixed multiset: inserted rows change the
selection's closures as a deployment's new rows do.
"""
from __future__ import annotations

import numpy as np


def zipf_weights(n_labels: int, zipf_a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_labels + 1) ** zipf_a
    return w / w.sum()


def label_members(n: int, n_labels: int, zipf_a: float, mean: float,
                  max_size: int, seed) -> np.ndarray:
    """[n, n_labels] bool: row i carries label j."""
    rng = np.random.default_rng(seed)
    sizes = np.clip(rng.poisson(mean, size=n), 0, min(max_size, n_labels))
    gumbel = -np.log(-np.log(rng.random((n, n_labels))))
    keys = np.log(zipf_weights(n_labels, zipf_a))[None, :] + gumbel
    order = np.argsort(-keys, axis=1)
    member = np.zeros((n, n_labels), bool)
    np.put_along_axis(member, order,
                      np.arange(n_labels)[None, :] < sizes[:, None], axis=1)
    return member


def as_tuples(member: np.ndarray) -> list[tuple[int, ...]]:
    """Label tuples (sorted ids) of each row of a membership matrix."""
    n_labels = member.shape[1]
    code = member.astype(np.int64) @ (1 << np.arange(n_labels, dtype=np.int64))
    uniq, inv = np.unique(code, return_inverse=True)
    table = [tuple(int(j) for j in range(n_labels) if c >> j & 1)
             for c in uniq]
    return [table[i] for i in inv]


def as_member(label_sets, n_labels: int) -> np.ndarray:
    out = np.zeros((len(label_sets), n_labels), bool)
    for i, labels in enumerate(label_sets):
        out[i, list(labels)] = True
    return out


def query_label_sets(base: list[tuple[int, ...]], n_queries: int,
                     seed) -> list[tuple[int, ...]]:
    """The paper's query generator (``generate_query_label_sets`` at
    ``from_base_fraction=1``): a random non-empty subset of the label set
    of a random row that has labels."""
    return draw_query_label_sets([b for b in base if b], n_queries, seed)


def draw_query_label_sets(nonempty: list[tuple[int, ...]], n_queries: int,
                          seed) -> list[tuple[int, ...]]:
    """:func:`query_label_sets` over the rows' non-empty label sets."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_queries):
        rng.random()   # the original's base-or-uniform coin; always base
        row = nonempty[rng.integers(len(nonempty))]
        size = rng.integers(1, len(row) + 1)
        chosen = rng.choice(len(row), size=int(size), replace=False)
        out.append(tuple(sorted(row[c] for c in chosen)))
    return out


def vectors(seed: int, stream: int, n: int, dim: int,
            index: int = 0) -> np.ndarray:
    """Gaussian f32 rows; ``stream`` and ``index`` keep the draws for the
    base rows and each call's queries apart."""
    rng = np.random.default_rng([seed, stream, index])
    return rng.standard_normal((n, dim), dtype=np.float32)


BASE, QUERY, ORDER, QUERY_LABELS, STREAM, STREAM_LABELS = 0, 2, 3, 4, 5, 6
BLOCK = 4096          # rows of the stream drawn together


class Dataset:
    """The rows of a run: the fixed multiset of label sets in the order
    ``--seed`` gives it, and Gaussian vectors from ``--seed``."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        n, L = cfg["n_rows"], cfg["n_labels"]
        member = label_members(n, L, cfg["zipf_a"],
                               cfg["mean_labels_per_row"],
                               cfg["max_labels_per_row"], cfg["label_seed"])
        pool = as_tuples(member)
        perm = np.random.default_rng([seed, ORDER]).permutation(n)
        self.member = member[perm]
        self.sets = [pool[i] for i in perm]
        self.nonempty = [s for s in self.sets if s]
        self.vectors = vectors(seed, BASE, n, cfg["dim"])
        self.n = n
        self._blocks: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def rows(self, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
        """(vectors [hi - lo, dim] f32, label membership [hi - lo, L]
        bool) of rows ``[lo, hi)`` of the row stream: the base rows below
        ``n``, then the stream's blocks."""
        if hi <= self.n:
            return self.vectors[lo:hi], self.member[lo:hi]
        vs, ms = [self.vectors[lo:self.n]], [self.member[lo:self.n]]
        start = max(lo, self.n)
        for b in range((start - self.n) // BLOCK,
                       (hi - 1 - self.n) // BLOCK + 1):
            v, m = self._block(b)
            b0 = self.n + b * BLOCK
            cut = slice(max(start, b0) - b0, min(hi, b0 + BLOCK) - b0)
            vs.append(v[cut])
            ms.append(m[cut])
        return np.concatenate(vs), np.concatenate(ms)

    def _block(self, b: int) -> tuple[np.ndarray, np.ndarray]:
        """Rows ``n + b * BLOCK`` onwards, ``BLOCK`` of them; the newest
        two blocks are kept, as inserts read the stream in order."""
        if b not in self._blocks:
            cfg = self.cfg
            self._blocks = {j: blk for j, blk in self._blocks.items()
                            if j == b - 1}
            self._blocks[b] = (
                vectors(self.seed, STREAM, BLOCK, cfg["dim"], b),
                label_members(BLOCK, cfg["n_labels"], cfg["zipf_a"],
                              cfg["mean_labels_per_row"],
                              cfg["max_labels_per_row"],
                              [self.seed, STREAM_LABELS, b]))
        return self._blocks[b]

    def queries(self, q: int, index: int):
        """(vectors, label sets) of the ``index``-th call's ``q`` queries:
        fresh draws of the paper's query generator over this run's rows."""
        qv = vectors(self.seed, QUERY, q, self.cfg["dim"], index)
        qls = draw_query_label_sets(self.nonempty, q,
                                    [self.seed, QUERY_LABELS, index])
        return qv, qls
