"""Serving launcher: batched decode + ELI label-hybrid retrieval (RAG).

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2_130m \
        --requests 12 --slots 4 [--no-rag]

Trains nothing: params are randomly initialized (reduced config) — the
point is the serving *engine*: slot-based continuous batching, per-request
label-filtered retrieval through the ELI-selected indexes, and generation.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from .. import arch as A
from ..configs import reduced_arch
from ..core.engine import LabelHybridEngine
from ..data.pipeline import VectorLabelDataset
from ..models.common import init_params
from ..serve import BatchedDecoder, Request, RetrievalAugmentedEngine
from . import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_130m")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--no-rag", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    spec = reduced_arch(args.arch)
    params = init_params(jax.random.PRNGKey(0), A.param_specs(spec))
    dec = BatchedDecoder(spec, params, batch_slots=args.slots,
                         max_len=args.max_len)

    rng = np.random.default_rng(0)
    reqs = []
    ds = VectorLabelDataset(n=4000, dim=16, n_labels=8)
    vectors, label_sets = ds.generate()
    _, qls = ds.queries(args.requests)
    for i in range(args.requests):
        prompt = rng.integers(0, spec.cfg.vocab, size=rng.integers(4, 12)
                              ).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new=args.max_new,
                            label_set=tuple(qls[i]), rid=i))

    if args.no_rag:
        done = dec.run(reqs)
        for r in sorted(done, key=lambda r: r.rid):
            print(f"[serve] req {r.rid}: generated {r.generated}")
        return

    eli = LabelHybridEngine.build(vectors, label_sets, mode="eis", c=0.2,
                                  backend="flat")
    rag = RetrievalAugmentedEngine(dec, eli, k=4)
    done = rag.serve(reqs)
    st = eli.stats()
    print(f"[serve] ELI: {st.n_selected} indexes, achieved c="
          f"{st.achieved_c:.2f}, {st.total_entries} entries")
    for r in sorted(done, key=lambda r: r.rid):
        print(f"[serve] req {r.rid} labels={r.label_set}: "
              f"neighbors={[int(x) for x in r.neighbors[:4]]} "
              f"generated={r.generated[:8]}...")


if __name__ == "__main__":
    main()
