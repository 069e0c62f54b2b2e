"""tier_pack_ms: mean host ms per search call of the program's own
``search.pack`` spans (``LabelHybridEngine.arena_tier_batches``: the
partition of a routed batch into span tiers, and each tier's sort and
padding).  Moves ``search_qps``."""


def read(ctx):
    spans = [e["dur"] for e in ctx.spans if e["name"] == "search.pack"]
    n_calls = sum(1 for c in ctx.calls if c.op == "search")
    if not spans or not n_calls:
        return None
    return sum(spans) / n_calls / 1e3       # spans are in microseconds
