"""Predicates with a tuple group whose other atoms the lineage walk
evaluated on the group's candidate rows, in % of all predicates with a
tuple group (``ScanStats`` ``tuple_rest_cand`` over it plus
``tuple_rest_table``, summed over the pipelines' engines in the window) -
lineage query, ``core/lineage.py``.  Nothing to read from a program without
the counters, or from a window in which no tuple group ran."""


def read(ctx):
    cand = ctx.scan.get("tuple_rest_cand")
    table = ctx.scan.get("tuple_rest_table")
    if cand is None or table is None or not cand + table:
        return None
    return 100.0 * cand / (cand + table)
