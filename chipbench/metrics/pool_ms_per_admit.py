"""Host milliseconds in the paged KV pool (``page_out`` + ``page_in``)
per admitted request, from the harness's spans around the pool."""


def read(data):
    c = data["counters"]
    if not c.get("admits"):
        return None
    return c["pool_s"] / c["admits"] * 1e3
