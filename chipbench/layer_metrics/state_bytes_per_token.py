"""What prefix caching costs a token in the state pool of a model with
linear-attention layers, as the pod reports it: ``GET /stats``'
``state_bytes_per_snapshot`` (a slot's bytes in every linear layer: the
matrices and the carried rows) over ``state_snapshot_tokens`` (the stride
between two snapshots of a sequence): 24.8 KiB at 512 tokens beside the
latent row's 1280 B. None where the program does not report them (a model
whose state rides in its pages reports ``state_bytes_per_token`` alone, and
this reader leaves it to the readers that were there)."""


def read(run):
    values = [
        s["state_bytes_per_snapshot"] / s["state_snapshot_tokens"]
        for s in run.stats_after
        if s.get("state_bytes_per_snapshot") and s.get("state_snapshot_tokens")
    ]
    if not values or len(values) != len(run.stats_after):
        return None
    return max(values)
