"""Tuple-membership groups of the lineage walk answered from the engine's
sorted-column index, in % of all tuple groups (``ScanStats``
``tuple_index_groups`` over it plus ``tuple_isin_groups``, summed over the
pipelines' engines in the window) - lineage query, ``core/lineage.py``.
Nothing to read from a program without the counters, or from a window in
which no tuple group ran."""


def read(ctx):
    index = ctx.scan.get("tuple_index_groups")
    isin = ctx.scan.get("tuple_isin_groups")
    if index is None or isin is None or not index + isin:
        return None
    return 100.0 * index / (index + isin)
