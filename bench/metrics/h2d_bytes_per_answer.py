"""Bytes the window's kernel launches and slab uploads sent to the device,
per answer finished in the window (``ScanStats.h2d_bytes``, summed over the
pipelines' engines) - scan routes, ``core/scan.py``.  Nothing to read from
a program without the counter."""


def read(ctx):
    sent = ctx.scan.get("h2d_bytes")
    return sent / ctx.answered if sent is not None and ctx.answered else None
