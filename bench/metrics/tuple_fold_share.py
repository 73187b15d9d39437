"""Atoms of the lineage walk's tuple groups that shared a left side with
another atom of their group and were folded into it before the index
probe, in % of all tuple-group atoms probed (``ScanStats`` ``tuple_folded``
over ``tuple_atoms``, summed over the pipelines' engines in the window) -
lineage query, ``core/lineage.py``.  Nothing to read from a program without
the counters, or from a window in which no tuple group ran."""


def read(ctx):
    atoms = ctx.scan.get("tuple_atoms")
    folded = ctx.scan.get("tuple_folded")
    if atoms is None or folded is None or not atoms:
        return None
    return 100.0 * folded / atoms
