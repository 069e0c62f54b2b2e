"""The read-only engine: ``LabelHybridEngine`` built once on the run's
rows, serving ``search``.  The adapter contract is in ``harness.py``."""
from __future__ import annotations

import numpy as np

import harness

ANNOTATIONS = {"search": "search_batched"}
ANSWERING = ("search",)


class Engine:
    def __init__(self, cfg: dict, ds, log):
        from repro.core.engine import LabelHybridEngine
        from repro.kernels import ops
        self.ds, self.log, self.k = ds, log, cfg["k"]
        self.target = LabelHybridEngine.build(
            ds.vectors, ds.sets, mode=cfg["selection"], c=cfg["c"],
            backend=cfg["index_backend"], metric=cfg["metric"],
            storage=cfg["storage"])
        self.programs = {"_segmented_topk": ops._segmented_topk}

    def warm(self, traffic: dict) -> None:
        """The engine's own warm-up over the Q-bucket ladder up to the
        largest call, as each call's fresh label sets spread its queries
        over the span tiers in numbers that change from call to call."""
        q_max = max(s["queries"] for s in traffic["step"])
        out = self.target.warmup_serving([self.k], min_bucket=1,
                                         max_batch=q_max)
        self.log(f"[warm] {out['programs']} programs over the Q-bucket "
                 f"ladder up to {q_max}: {out['seconds']:.1f}s")

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

    def place(self, ids: np.ndarray) -> np.ndarray:
        """Ids are the rows themselves; the sentinel ``n`` is empty."""
        return np.where(ids < self.ds.n, ids, -1)

    def live(self) -> tuple[int, int]:
        return 0, self.ds.n
