"""Mean seconds of a one-row ``PredTrace.query`` call in the window (the
lineage walk of one output row and its scans), in ms - lineage query
layer, ``core/lineage.py``."""


def read(ctx):
    secs = [s for k, s in ctx.queries if k == 1]
    return 1e3 * sum(secs) / len(secs) if secs else None
