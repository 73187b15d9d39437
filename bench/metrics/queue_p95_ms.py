"""95th percentile of the time a request waited in the service's queue, in
ms: its ``service.queue`` span runs from enqueue to the dispatcher's
dequeue (``repro.core.trace``) - service queue and coalescer,
``core/service.py``.  Nothing to read without program spans."""

import numpy as np


def read(ctx):
    waits = [s.end_ns - s.start_ns for s in getattr(ctx, "spans", None) or ()
             if s.name == "service.queue"]
    return float(np.percentile(waits, 95)) * 1e-6 if waits else None
