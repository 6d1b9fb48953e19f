"""Share of the window's decode dispatches whose input ids came from the
burst in flight, on the device: ``Engine.step_stats``'
``decode_chained_dispatches`` over ``decode_dispatches`` (counted where the
dispatch is made; on in the traced run only), all replicas together, in per
cent. Such a dispatch was enqueued before the tokens of the one before it
were fetched, so the host's work of a step ran while the device did; every
other dispatch waited for that fetch first. The engine runs ahead only
where nothing could be admitted at the next step and no lane is within a
burst of its budget: near 100 says the cell keeps its lanes full, near 0
that a lane is nearly always free (and no first token waits for a burst)."""


def read(run):
    chained = dispatches = 0
    for after, before in zip(run.step_after, run.step_before):
        if (
            "decode_chained_dispatches" not in after
            or "decode_chained_dispatches" not in before
        ):
            return None  # a program that does not count them
        chained += (
            after["decode_chained_dispatches"] - before["decode_chained_dispatches"]
        )
        dispatches += after["decode_dispatches"] - before["decode_dispatches"]
    return 100.0 * chained / dispatches if dispatches else None
