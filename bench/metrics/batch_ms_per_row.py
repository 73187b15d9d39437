"""Seconds the service's dispatcher spent in ``PredTrace.query_batch``
calls (the lineage walk of a coalesced batch and its scans), per row those
calls answered, in ms - lineage query layer, ``core/lineage.py``.  Nothing
to read in a window that coalesced no batch."""


def read(ctx):
    rows = sum(k for k, _ in ctx.queries if k > 1)
    secs = sum(s for k, s in ctx.queries if k > 1)
    return 1e3 * secs / rows if rows else None
