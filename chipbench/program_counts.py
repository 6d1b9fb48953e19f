"""What the readers of the fused decode path's counters share: the window's
growth of ``Engine.step_stats``' counters (on in the traced run only), all
replicas together, and ``/stats``' ``routed_layers``. None where the program
does not count one of them (a program from before the counter)."""

KEYS = ("experts_touched", "decode_forwards", "decode_dispatches",
        "decode_rows", "attn_ctx_tokens")


def deltas(run):
    out = dict.fromkeys(KEYS, 0)
    for after, before in zip(run.step_after, run.step_before):
        for key in KEYS:
            if key not in after or key not in before:
                return None
            out[key] += after[key] - before[key]
    layers = {stats.get("routed_layers") for stats in run.stats_after}
    if len(layers) != 1 or None in layers:
        return None
    out["routed_layers"] = layers.pop()
    return out


def experts_touched_per_layer(counts):
    """Mean number of distinct experts the rows of one forward chose in one
    routed layer; None where nothing was counted (no forward, no routed
    layer, a decode path that does not count)."""
    if not (counts["experts_touched"] and counts["decode_forwards"]
            and counts["routed_layers"]):
        return None
    return (counts["experts_touched"] / counts["decode_forwards"]
            / counts["routed_layers"])
