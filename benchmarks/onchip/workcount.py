"""The least work of a filtered scan, and the chip's peaks.

The count belongs to the benchmark, not to any kernel: it is the work any
implementation of the scan has to do for one search call, so no kernel
can read above its roofline.  For a call whose real queries were routed
to segments (each segment the arena rows carrying its key's labels):

- bytes: every arena row that some routed segment covers, counted once,
  at ``D * dtype + label words + norm`` bytes, the label words being the
  fewest 32-bit words that hold the label universe; the row ids of every
  distinct routed segment at 4 bytes each; the queries (vector and label
  words); and the outputs (distance, position and id per result);
- operations: ``2 * D`` per (query, row of its routed segment).

The least time is the larger of bytes over the HBM bandwidth and
operations over the bf16 peak; the bf16 peak also stands for float32
work, which can only lower the share.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def key_labels(key) -> list[int]:
    """Label ids of a selected key (the 64-bit words of a label bitmask,
    as the program's ``QueryCard.selected_key`` holds them)."""
    return [64 * w + b for w, word in enumerate(key) for b in range(64)
            if int(word) >> b & 1]


def segment_mask(member: np.ndarray, key_labels) -> np.ndarray:
    """Rows of ``member`` (bool [N, L]) carrying every label of the key."""
    key_labels = list(key_labels)
    if not key_labels:
        return np.ones(member.shape[0], bool)
    return member[:, key_labels].all(axis=1)


def scan_work(member: np.ndarray, routed: list[tuple[tuple[int, ...], int]],
              *, dim: int, dtype_bytes: int, label_words: int,
              k: int) -> dict:
    """Least bytes and operations of one call.  ``member`` is the arena's
    label membership; ``routed`` lists (routed key as label ids, real
    queries routed to it)."""
    per_key: dict[tuple[int, ...], int] = {}
    for labels, count in routed:
        per_key[tuple(labels)] = per_key.get(tuple(labels), 0) + count
    union = np.zeros(member.shape[0], bool)
    ops = 0
    id_bytes = 0
    n_queries = 0
    for labels, count in per_key.items():
        seg = segment_mask(member, labels)
        rows = int(seg.sum())
        union |= seg
        ops += 2 * dim * rows * count
        id_bytes += 4 * rows
        n_queries += count
    row_bytes = dim * dtype_bytes + 4 * label_words + 4
    nbytes = (int(union.sum()) * row_bytes + id_bytes
              + n_queries * (dim * 4 + 4 * label_words)
              + n_queries * k * 12)
    return {"bytes": nbytes, "ops": ops}


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """(least time, which bound binds: "bytes" or "ops")."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["ops"] / peak["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
