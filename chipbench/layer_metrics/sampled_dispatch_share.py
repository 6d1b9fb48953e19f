"""Share of the window's decode dispatches that carried a lane with
``temperature > 0``: ``Engine.step_stats``' ``decode_sampled_dispatches``
over ``decode_dispatches`` (counted where the dispatch is made; on in the
traced run only), all replicas together, in per cent. In these dispatches
the sampler's gate (``ops/sampling.py``) runs its full-vocabulary filter for
every row; in the others the program executes ``argmax`` alone. 0.0 says the
cell bypasses the filter altogether."""


def read(run):
    sampled = dispatches = 0
    for after, before in zip(run.step_after, run.step_before):
        if (
            "decode_sampled_dispatches" not in after
            or "decode_sampled_dispatches" not in before
        ):
            return None  # a program that does not count them
        sampled += (
            after["decode_sampled_dispatches"] - before["decode_sampled_dispatches"]
        )
        dispatches += after["decode_dispatches"] - before["decode_dispatches"]
    return 100.0 * sampled / dispatches if dispatches else None
