"""The mutable engine: ``StreamingEngine`` built on the run's base rows,
serving ``search``, ``insert`` and ``delete`` over the row stream
(``gen.Dataset.rows``).  The adapter contract is in ``harness.py``.

- ``{"op": "search", "queries": Q}``: ``search_batched`` of Q fresh
  queries, as the static engine's;
- ``{"op": "insert", "rows": m}``: inserts the next m rows of the stream;
- ``{"op": "delete", "rows": m}``: deletes the oldest m live rows.

A sliding window is one insert and one delete of equal size per step.
So the live rows are always one range ``[lo, hi)`` of the stream.

The id book maps each engine id to its row of the stream: ``insert``'s
returned ids, then, at each compaction the engine records in its
``compaction_log``, the record's ``id_map`` (old id to new, -1 for a row
dropped).  A deleted row keeps its place in the book until the compaction
drops it, so an answer that brings it back is placed outside the live
range.

The configuration may give ``max_delta_fraction``,
``max_tombstone_fraction`` and ``max_delta_capacity``; the engine's own
defaults hold for a key it leaves out."""
from __future__ import annotations

import numpy as np

import gen
import harness

ANNOTATIONS = {"search": "search_batched", "insert": "insert",
               "delete": "delete"}
ANSWERING = ("search",)
THRESHOLDS = ("max_delta_fraction", "max_tombstone_fraction",
              "max_delta_capacity")


class Engine:
    def __init__(self, cfg: dict, ds, log):
        from repro.core.stream import StreamingEngine
        from repro.kernels import ops
        self.ds, self.log, self.k = ds, log, cfg["k"]
        self.target = StreamingEngine.build(
            ds.vectors, ds.sets, mode=cfg["selection"], c=cfg["c"],
            backend=cfg["index_backend"], metric=cfg["metric"],
            storage=cfg["storage"],
            **{key: cfg[key] for key in THRESHOLDS if key in cfg})
        self.programs = {"_segmented_topk": ops._segmented_topk,
                         "scatter_topk_rows": ops.scatter_topk_rows,
                         "_merge_topk": ops._merge_topk}
        self.lo, self.hi = 0, ds.n
        self.eid = np.arange(ds.n, dtype=np.int64)   # of rows [lo, hi)
        self.row = np.arange(ds.n, dtype=np.int64)   # of each engine id
        self.n_log = 0

    def warm(self, traffic: dict) -> None:
        """The engine's serving warm-up over the Q-bucket ladder up to the
        largest search, with the delta-scan programs of every capacity
        tier the delta fills through: up to the fill trigger, or the
        largest insert where that is more."""
        steps = traffic["step"]
        q_max = max(s["queries"] for s in steps if s["op"] == "search")
        eng = self.target
        trigger = (int(eng.max_delta_fraction * self.ds.n)
                   if eng.max_delta_fraction is not None else 0)
        hint = max([trigger] + [s["rows"] for s in steps
                                if s["op"] == "insert"])
        out = eng.warmup_serving([self.k], min_bucket=1, max_batch=q_max,
                                 delta_rows_hint=hint)
        self.log(f"[warm] {out['programs']} programs over the Q-bucket "
                 f"ladder up to {q_max} and delta tiers to {hint} rows: "
                 f"{out['seconds']:.1f}s")

    def search(self, spec: dict, index: int, timed) -> harness.Call:
        """``search_batched`` of ``spec["queries"]`` fresh queries, the
        ``index``-th call's draws (``gen.Dataset.queries``)."""
        q = spec["queries"]
        qv, qls = self.ds.queries(q, index)
        (d, ids), dt = timed(self.target.search_batched, qv, qls, self.k)
        return harness.Call("search", dt, queries=q,
                            answer={"qv": qv, "qls": qls,
                                    "ids": np.asarray(ids, np.int64),
                                    "d": np.asarray(d)})

    def insert(self, spec: dict, index: int, timed) -> harness.Call:
        m = spec["rows"]
        x, member = self.ds.rows(self.hi, self.hi + m)
        ids, dt = timed(self.target.insert, x, gen.as_tuples(member))
        self._renumber()        # a fill-triggered compaction runs first
        ids = np.asarray(ids, np.int64)
        self.eid = np.concatenate([self.eid, ids])
        if ids.size and ids.max() >= len(self.row):
            self.row = np.concatenate([self.row, np.full(
                ids.max() + 1 - len(self.row), -1, np.int64)])
        self.row[ids] = np.arange(self.hi, self.hi + m)
        self.hi += m
        return harness.Call("insert", dt)

    def delete(self, spec: dict, index: int, timed) -> harness.Call:
        m = min(spec["rows"], self.hi - self.lo)
        _, dt = timed(self.target.delete, self.eid[:m])
        self.eid = self.eid[m:]
        self.lo += m
        self._renumber()
        return harness.Call("delete", dt)

    def _renumber(self) -> None:
        """Apply each compaction the engine has logged since the last
        look to the book."""
        log = self.target.compaction_log
        for rec in log[self.n_log:]:
            id_map = rec["id_map"]
            self.eid = id_map[self.eid]
            row = np.full(rec["n"], -1, np.int64)
            kept = id_map >= 0
            row[id_map[kept]] = self.row[:len(id_map)][kept]
            self.row = row
        self.n_log = len(log)

    def place(self, ids: np.ndarray) -> np.ndarray:
        """Each id's row of the stream; -1 for the sentinel or an id the
        book does not hold."""
        known = (ids >= 0) & (ids < len(self.row))
        return np.where(known, self.row[np.where(known, ids, 0)], -1)

    def live(self) -> tuple[int, int]:
        return self.lo, self.hi
