"""Host time of the lineage walk outside scans, per lineage call, in ms:
the self time (duration less what child spans cover) of every
``lineage.*`` span under a root ``lineage.query`` / ``lineage.query_batch``
span, summed per root and averaged over roots (``repro.core.trace``) -
lineage query, ``core/lineage.py``.  Nothing to read without program
spans."""


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if not spans:
        return None
    from repro.core.trace import self_ns

    own = self_ns(spans)
    by_id = {s.id: s for s in spans}
    walk = {}
    for s in spans:
        if not s.name.startswith("lineage."):
            continue
        root = s
        while (root.parent in by_id
               and by_id[root.parent].name.startswith("lineage.")):
            root = by_id[root.parent]
        walk[root.id] = walk.get(root.id, 0) + own[s.id]
    return 1e-6 * sum(walk.values()) / len(walk) if walk else None
