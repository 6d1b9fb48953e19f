"""What the readers of a held share's counters share: ``program_counts``'
window growth of the fused decode path's counters (``Engine.step_stats``, on
in the traced run only, all replicas together) with the places a routed
layer counts beside ``experts_touched`` and ``/stats``' ``experts_held`` /
``zero_experts``. None where the program does not count one of them (a
program from before the counters, as the parent of the PR that added them)
or nothing was routed in the window."""

from chipbench import program_counts

PLACES = ("routed_places", "zero_places", "held_places")
STATS = ("experts_held", "zero_experts")


def deltas(run):
    out = program_counts.deltas(run)
    if out is None:
        return None
    for key in PLACES:
        out[key] = 0
        for after, before in zip(run.step_after, run.step_before):
            if key not in after or key not in before:
                return None
            out[key] += after[key] - before[key]
    for name in STATS:
        values = {stats.get(name) for stats in run.stats_after}
        if len(values) != 1 or None in values:
            return None
        out[name] = values.pop()
    if not (out["routed_layers"] and out["decode_forwards"]
            and out["routed_places"]):
        return None
    return out


def layer_forwards(counts) -> int:
    """Forwards of one routed layer the counters were summed over."""
    return counts["decode_forwards"] * counts["routed_layers"]
