"""Bytes the window's kernel launches brought back from the device (their
``[K, N]`` int32 masks), per answer finished in the window
(``ScanStats.d2h_bytes``, summed over the pipelines' engines) - scan
routes, ``core/scan.py``.  Nothing to read from a program without the
counter."""


def read(ctx):
    got = ctx.scan.get("d2h_bytes")
    return got / ctx.answered if got is not None and ctx.answered else None
