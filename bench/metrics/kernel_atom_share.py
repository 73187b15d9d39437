"""Comparison and membership atoms a kernel launch evaluated, in % of all
atoms evaluated over a table's rows (``ScanStats`` ``kernel_atoms`` over it
plus ``host_atoms``, summed over the pipelines' engines in the window) -
scan routes, ``core/scan.py``.  A column-pair or decimal atom on its lane
counts as a kernel atom; an atom whose parameter is bound to a set stays
on the host.  Nothing to read from a program without the counters, or
from a window in which no atom was evaluated."""


def read(ctx):
    kernel = ctx.scan.get("kernel_atoms")
    host = ctx.scan.get("host_atoms")
    if kernel is None or host is None or not kernel + host:
        return None
    return 100.0 * kernel / (kernel + host)
