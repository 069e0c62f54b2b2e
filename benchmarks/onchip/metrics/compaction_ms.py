"""compaction_ms: mean host ms per compaction of the streaming engine, its
``stream.compact`` span (``StreamingEngine.flush``: the survivors, the
incremental GroupTable, the fold from the host mirrors, the upload of the
new arena and the rebase), over the window.  None where the window holds
no compaction.  Moves ``search_qps``."""


def read(ctx):
    spans = [e["dur"] for e in ctx.spans if e["name"] == "stream.compact"]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e3      # spans are in microseconds
