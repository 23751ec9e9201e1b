"""Bytes the object store (``core/store.py``) took in and gave out over the
window, per request answered, from ``ObjectStore.stats``."""


def read(ctx):
    done = ctx.records["requests_done"]
    return ctx.records["store_bytes"] / done if done else None
