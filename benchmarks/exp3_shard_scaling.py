"""Exp-3 — thread scaling becomes shard scaling on the TPU mesh.

The DistributedFlatIndex shards rows over the 'data' axis of a mesh of
1, 2, 4, 8 devices (as many as exist: on a CPU host run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and merges
per-shard top-k with one all-gather.  Runs in this process.  Reported:
per-shard-count QPS + recall (merge correctness) + the collective payload
(2·S·k·8 bytes per query — N-independent).
"""
import jax

from repro.compat import make_mesh
from repro.core.labels import encode_many, masks_to_int32_words
from repro.index.distributed import DistributedFlatIndex

from .common import emit, ground_truth, make_dataset, measure


class _Searcher:
    def __init__(self, ix):
        self.ix = ix

    def search(self, qv, qls, k):
        return self.ix.search(qv, masks_to_int32_words(encode_many(qls)), k)


def run():
    x, ls, qv, qls = make_dataset(n=16_000, q=96)
    gt_d, gt_i = ground_truth(x, ls, qv, qls, 10)
    words = masks_to_int32_words(encode_many(ls))
    rows = []
    for s in (1, 2, 4, 8):
        if s > len(jax.devices()):
            break
        mesh = make_mesh((s,), ("data",), devices=jax.devices()[:s])
        ix = DistributedFlatIndex(x, words, mesh)
        qps, rec, us = measure(_Searcher(ix), qv, qls, 10, gt_i, len(ls))
        rows.append({"name": f"exp3/shards={s}",
                     "us_per_call": round(us, 1), "qps": round(qps),
                     "recall": round(rec, 4),
                     "collective_bytes_per_q": 2 * s * 10 * 8})
    emit(rows, "exp3")
    return rows


if __name__ == "__main__":
    run()
