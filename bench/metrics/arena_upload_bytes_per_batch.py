"""Bytes of arena plane uploaded to the device per query batch
(``cache_stats()["device_upload_bytes"]``: the whole plane, each time a
gather finds that it changed)."""


def snapshot(svc):
    return svc.registry.cache_stats().get("device_upload_bytes")


def read(run, before, after):
    batches = len(run.requests("query"))
    if before is None or after is None or not batches:
        return None
    return (after - before) / batches
