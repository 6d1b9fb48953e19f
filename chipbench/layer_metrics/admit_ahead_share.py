"""Share of the window's admissions whose prefill was dispatched behind the
decode burst that ended their predecessor, so that the host's work of the
admission (the prompt's hash chain, the walk through the cache, the pages, the
prefill's inputs and their upload) ran while the device did:
``Engine.step_stats``' ``admit_ahead`` over ``admit_attempts -
admit_rollbacks`` (the admissions that stood; counted where they happen; on in
the traced run only), all replicas together, in per cent. The engine admits
ahead only where a lane is certain to leave within the burst just enqueued, a
request waits and its pages are free without the leaving lane's: near 100 says
the cell keeps its lanes full with somebody waiting and every finish is by the
token budget, near 0 that an arrival finds a lane free (an open loop under its
capacity) or that lanes end by stop tokens nobody foresees. None for a program
that does not count them, and where nothing was admitted."""


def read(run):
    ahead = stood = 0
    for after, before in zip(run.step_after, run.step_before):
        if "admit_ahead" not in after or "admit_ahead" not in before:
            return None  # a program that does not count them
        ahead += after["admit_ahead"] - before["admit_ahead"]
        stood += (
            after["admit_attempts"] - after["admit_rollbacks"]
            - before["admit_attempts"] + before["admit_rollbacks"]
        )
    return 100.0 * ahead / stood if stood else None
