"""Bytes one token slot holds in the sliding layers' window pools, all such
layers, as the pod reports them in ``GET /stats`` (``window_bytes_per_token``:
the pools' bytes as held on the device over their token slots, computed once
at the engine's construction): 4 x 4096 = 16384 in ``longdocs``, beside
``kv_bytes_per_token`` 4096 for the one full layer. Together they pin what
the cache costs. None where the program does not report the key (a program
from before it) or the model has no such layer (the key reads 0)."""


def read(run):
    values = [s.get("window_bytes_per_token") for s in run.stats_after]
    if not values or any(not v for v in values):
        return None
    return max(values)
