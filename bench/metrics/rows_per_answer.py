"""Rows covered by the window's scan calls (engine scans, batch scans times
their bindings, stored-stage scans), per answer finished in the window,
from the ``bench.scan`` spans of a traced run - scan routes,
``core/scan.py`` and ``core/store.py``."""


def read(ctx):
    if not ctx.answered or not ctx.rows_scanned:
        return None
    return ctx.rows_scanned / ctx.answered
