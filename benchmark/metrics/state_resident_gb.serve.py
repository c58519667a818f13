"""Bytes of recurrent state resident on the device, the cache
manager's own counter (`RecurrentStateCache.pool_bytes`: every slot's
block of every layer, held whether or not a request is in the slot),
in GB (1e9 bytes). None for a model whose cache is pages."""


def read(ctx):
    nbytes = ctx.get("state_resident_bytes")
    return None if nbytes is None else nbytes / 1e9
