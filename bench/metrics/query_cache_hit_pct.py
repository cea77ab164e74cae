"""Answer-cache (per-tenant LRU) hits over lookups in the window, from
``registry.cache_stats()``."""


def snapshot(svc):
    return svc.registry.cache_stats()


def read(run, before, after):
    hits = after["hits"] - before["hits"]
    looked = hits + after["misses"] - before["misses"]
    return None if looked == 0 else 100.0 * hits / looked
