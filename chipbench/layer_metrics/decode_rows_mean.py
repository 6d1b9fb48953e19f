"""Real lanes in a decode dispatch, mean over the window's dispatches:
``Engine.step_stats``' ``decode_rows`` over ``decode_dispatches`` (counted
where the dispatch is made; on in the traced run only), all replicas
together. Of the cell's lanes, the rest of each dispatch is padding."""


def read(run):
    rows = dispatches = 0
    for after, before in zip(run.step_after, run.step_before):
        if "decode_rows" not in after or "decode_rows" not in before:
            return None  # a program that does not count them
        rows += after["decode_rows"] - before["decode_rows"]
        dispatches += after["decode_dispatches"] - before["decode_dispatches"]
    return rows / dispatches if dispatches else None
