"""Bytes one token slot holds in a pool, all layers, as the pod reports them
in ``GET /stats`` (the pools' bytes as held on the device over their token
slots, computed once at the engine's construction). ``.kv``:
``kv_bytes_per_token``, the keys and values of the layers that attend (3 x
2048 = 6144 in ``agentloop``: two KV heads of 64 share a 128-lane row, so
nothing is padded). ``.state``: ``state_bytes_per_token``, the convolution
layers' state slots of a page over its tokens (11 x 8192 / 16 = 5632).
Together they pin what the cache costs. None where the program does not
report the key (a program from before it)."""

KEYS = {"kv": "kv_bytes_per_token", "state": "state_bytes_per_token"}


def read(run, suffix):
    values = [s.get(KEYS[suffix]) for s in run.stats_after]
    if not values or any(v is None for v in values):
        return None
    return max(values)
