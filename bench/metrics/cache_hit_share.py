"""Answer-cache hits over lookups, in % (``ServiceStats``) - the service's
answer cache, ``core/service.py``."""


def read(ctx):
    s = ctx.service
    looked = s["cache_hits"] + s["cache_misses"]
    return 100.0 * s["cache_hits"] / looked if looked else None
