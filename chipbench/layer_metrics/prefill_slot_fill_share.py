"""Real tokens over the token slots the window's prefill dispatches computed,
per cent: a dispatch computes the rows that hold a sequence (as far as the
last valid one reaches of its ``max_prefill_batch``, 8) of a bucketed width,
and the program counts both sides in ``/stats``' ``prefill`` block, always
(``tokens_computed``, ``token_slots`` = rows computed x width). One less this
share is the padding (the width's bucket and the shorter rows' tails): what
the routed FFN leaves out of its expert groups and what attention's
projections and a dense FFN still compute. None for a window without a
prefill dispatch and for a program that does not count its slots."""


def read(run):
    def grew(key):
        return sum(
            a["prefill"].get(key, 0) - b["prefill"].get(key, 0)
            for a, b in zip(run.stats_after, run.stats_before)
        )

    slots = grew("token_slots")
    if not slots:
        return None
    return 100.0 * grew("tokens_computed") / slots
