"""scan_useful_share: rows of the routed segments (their true lengths,
real queries only) over the rows the padded span tiers scan (Q-bucket x
span tier, ``LabelHybridEngine.arena_tier_batches``), over the window's
search calls.  Routed keys, span tiers and Q-buckets come from the
program's ``QueryCard``s; each segment's length is counted by the
benchmark from its own copy of the rows' labels.  Moves ``search_qps``."""

import workcount


def read(ctx):
    useful = padded = 0
    for c in ctx.calls:
        if c.op != "search" or not c.cards:
            continue
        tiers = {}
        for key, n_queries, span_tier, q_bucket in c.cards:
            seg = workcount.segment_mask(ctx.member,
                                         workcount.key_labels(key))
            useful += n_queries * int(seg.sum())
            tiers[span_tier] = q_bucket
        padded += sum(t * b for t, b in tiers.items())
    if not padded:
        return None
    return 100.0 * useful / padded
