"""mutation_ms: mean host ms per insert or delete call of the streaming
engine, from its ``stream.insert`` and ``stream.delete`` spans, less the
``stream.compact`` spans nested in them: the acknowledgement path, which
is the delta append and the tombstone upload.  None where the window
holds no mutation span.  Moves ``search_qps``."""

MUTATIONS = ("stream.insert", "stream.delete")


def read(ctx):
    muts = [e for e in ctx.spans if e["name"] in MUTATIONS]
    if not muts:
        return None
    compacts = [e for e in ctx.spans if e["name"] == "stream.compact"]
    total = 0.0
    for m in muts:
        lo, hi = m["ts"], m["ts"] + m["dur"]
        nested = sum(c["dur"] for c in compacts
                     if lo <= c["ts"] and c["ts"] + c["dur"] <= hi)
        total += m["dur"] - nested
    return total / len(muts) / 1e3            # spans are in microseconds
