"""route_ms: mean host time per search call of the program's own
``search.route`` span (``LabelHybridEngine.route_many``: encoding the
query label sets and routing each to its selected index).  Moves
``search_qps``."""


def read(ctx):
    spans = [e["dur"] for e in ctx.spans if e["name"] == "search.route"]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e3      # spans are in microseconds
