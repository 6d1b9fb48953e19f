"""What the readers of the block-diffusion counters share: the window's
growth of ``Engine.step_stats``' counters (counted in
``Engine._run_decode_block``; on in the traced run only), all replicas
together. None where the program does not count them (a program from before
they were there, or a model that does not generate by blocks: no dispatch)."""

KEYS = ("denoise_lane_forwards", "commit_lane_forwards", "block_tokens_fixed",
        "blocks_final")


def deltas(run):
    out = dict.fromkeys(KEYS, 0)
    for after, before in zip(run.step_after, run.step_before):
        for key in KEYS:
            if key not in after or key not in before:
                return None
            out[key] += after[key] - before[key]
    out["lane_forwards"] = (out["denoise_lane_forwards"]
                            + out["commit_lane_forwards"])
    return out if out["lane_forwards"] else None


def experts_touched_per_layer(run):
    """Mean number of distinct experts the rows of one forward chose in one
    layer: the window's growth of ``experts_touched`` (counted on the
    device, summed over the layers) over that of ``decode_dispatches`` and
    the model's layers. None where the program does not count it, or counted
    none (no dispatch; an FFN that is not the routed one)."""
    total = dispatches = 0
    for after, before in zip(run.step_after, run.step_before):
        for key in ("experts_touched", "decode_dispatches"):
            if key not in after or key not in before:
                return None
        total += after["experts_touched"] - before["experts_touched"]
        dispatches += after["decode_dispatches"] - before["decode_dispatches"]
    if not total or not dispatches:
        return None
    return total / dispatches / run.model_cfg.n_layers
