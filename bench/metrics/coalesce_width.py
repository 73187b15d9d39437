"""Requests the service folded into each batch it dispatched
(``ServiceStats``: coalesced requests over batches) - service queue and
coalescer, ``core/service.py``."""


def read(ctx):
    s = ctx.service
    return s["coalesced_requests"] / s["batches"] if s["batches"] else None
