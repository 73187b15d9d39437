"""Kernel launches per scan call, in % (``ScanEngine.stats()``:
``device_scans`` over ``scans`` + ``batch_scans``, summed over the
pipelines' engines in the window) - scan routes, ``core/scan.py``.  A scan
answered on the host (a float64 atom, the sorted-column pivot of a batch)
launches nothing; a run-space RLE scan may launch twice."""


def read(ctx):
    s = ctx.scan
    calls = s.get("scans", 0) + s.get("batch_scans", 0)
    return 100.0 * s.get("device_scans", 0) / calls if calls else None
