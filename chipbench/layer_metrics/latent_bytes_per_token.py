"""Bytes one token holds in the pools, all layers, as the pod reports them:
``GET /stats``' ``kv_bytes_per_token`` (the pools' bytes as held on the
device over their token slots, computed once at the engine's construction).
It pins what the cache costs: 8 x 1280 for 8 layers of latent rows held in
640 values, no second pool (8 x 1152 if a row were held unpadded). None
where the program does not report it."""


def read(run):
    values = [s.get("kv_bytes_per_token") for s in run.stats_after]
    if not values or any(v is None for v in values):
        return None
    return max(values)
