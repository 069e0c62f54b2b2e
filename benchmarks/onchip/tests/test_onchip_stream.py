"""The streaming engine driven through the harness by files alone: a
stream configuration and a sliding-window traffic mix added under a
test checkout as new files and ``BENCHMARK.json`` entries, run through
``engines/stream.py`` and held against the rows live at each search;
three faults of the mutation path that must each come out not correct;
and the row stream of ``gen`` that the check regenerates rows from."""
import json

import numpy as np
import onchip_testkit as kit
import pytest

import gen

STREAM = "fixture-stream.sliding"
N_ROWS = 4000


def stream_root(tmp):
    """A test checkout with a stream cell beside the static one: a
    sliding window of 64 inserts and 64 deletes per step, a search of 32,
    and a delta-fill trigger at 5% of the rows, so compactions fall
    inside a short window."""
    root = kit.tiny_root(tmp, n_rows=N_ROWS)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / bench["paths"][0]
    cfg = json.loads((root / bench["configs"][0]["file"]).read_text())
    cfg.update(name="fixture-stream", engine="stream",
               max_delta_fraction=0.05, max_tombstone_fraction=0.25)
    (bench_dir / "configs/fixture-stream.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic/fixture-sliding.json").write_text(json.dumps(
        {"step": [{"op": "insert", "rows": 64},
                  {"op": "delete", "rows": 64},
                  {"op": "search", "queries": 32}],
         "check": {"searches": 2, "queries": 32}}))
    bench["configs"].append(dict(bench["configs"][0], name="fixture-stream",
                                 file=f"{bench['paths'][0]}/configs/"
                                      "fixture-stream.json"))
    bench["workloads"].append({"name": STREAM, "config": "fixture-stream",
                               "traffic": "fixture-sliding", "chips": 1,
                               "why": "a fixture"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_sliding_window_is_correct_through_compactions(tmp_path):
    engines, lines = [], []
    out = kit.run(stream_root(tmp_path), STREAM, seed=3150000001,
                  seconds=2.0, fault=engines.append, log=lines.append)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0
    log = engines[0].compaction_log
    assert log and all(rec["folded_rows"] > 0 for rec in log)
    assert any(line.startswith("[window] compiles inside the window: ")
               and "_merge_topk" in line for line in lines)


def drop_inserts(monkeypatch):
    """Each insert is acknowledged with its ids, and its rows never become
    searchable: they are deleted as they land."""
    def plant(target):
        real = target.insert

        def dropped(vectors, label_sets):
            ids = real(vectors, label_sets)
            target.delete(ids)
            return ids
        monkeypatch.setattr(target, "insert", dropped)
    return plant


def keep_deleted(monkeypatch):
    """Each delete is acknowledged and not forwarded: the rows stay."""
    def plant(target):
        monkeypatch.setattr(target, "delete", lambda ids: len(ids))
    return plant


class _Unread(list):
    def append(self, rec):
        pass


def stale_book(monkeypatch):
    """The engine compacts, but the adapter's id book never sees the
    renumbering (the compaction log stays empty)."""
    def plant(target):
        monkeypatch.setattr(target, "compaction_log", _Unread())
    return plant


@pytest.mark.parametrize("fault", [drop_inserts, keep_deleted, stale_book],
                         ids=["insert-dropped", "delete-not-forwarded",
                              "id-book-stale"])
def test_a_broken_mutation_path_is_not_correct(tmp_path, monkeypatch,
                                               fault):
    out = kit.run(stream_root(tmp_path), STREAM, seed=3150000002,
                  seconds=2.0, fault=fault(monkeypatch))
    assert out["correct"] is False
    failed = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed
    if fault is keep_deleted:
        assert out["checks"]["bad_queries"]["value"] > 0


CFG = {"n_rows": 3000, "n_labels": 8, "zipf_a": 1.5,
       "mean_labels_per_row": 3.0, "max_labels_per_row": 8,
       "label_seed": 4, "dim": 8}
LO, HI = 2990, 3000 + 2 * gen.BLOCK + 10


@pytest.mark.parametrize("cuts", [
    (), (3000,), (3000 + gen.BLOCK,), (2995, 3001, 3000 + gen.BLOCK - 1),
    tuple(range(LO + 700, HI, 700)),
], ids=["whole", "at-base-end", "at-block-edge", "around-edges", "strided"])
def test_row_stream_regenerates_a_range_however_split(cuts):
    whole_v, whole_m = gen.Dataset(CFG, 2**31 + 5).rows(LO, HI)
    ds = gen.Dataset(CFG, 2**31 + 5)
    edges = (LO,) + cuts + (HI,)
    parts = [ds.rows(a, b) for a, b in zip(edges, edges[1:])]
    assert np.array_equal(np.concatenate([v for v, _ in parts]), whole_v)
    assert np.array_equal(np.concatenate([m for _, m in parts]), whole_m)
    assert whole_v.shape == (HI - LO, 8) and whole_v.dtype == np.float32


@pytest.mark.parametrize("seed", [12, 2**31 + 11])
def test_base_rows_are_the_dataset_draws_and_stream_labels_are_fresh(seed):
    ds = gen.Dataset(CFG, seed)
    n = CFG["n_rows"]
    v, m = ds.rows(0, n)
    assert np.array_equal(v, gen.vectors(seed, gen.BASE, n, CFG["dim"]))
    fixed = gen.label_members(n, 8, 1.5, 3.0, 8, CFG["label_seed"])
    perm = np.random.default_rng([seed, gen.ORDER]).permutation(n)
    assert np.array_equal(m, fixed[perm])
    # past the base: fresh draws of the paper's generator from the seed
    _, stream = ds.rows(n, n + gen.BLOCK)
    _, other = gen.Dataset(CFG, seed + 1).rows(n, n + gen.BLOCK)
    assert not np.array_equal(stream, other)
    p_base, p_stream = fixed.mean(axis=0), stream.mean(axis=0)
    assert np.all(np.abs(p_base - p_stream) < 0.05)
    assert p_stream[0] > 3 * p_stream[-1]
