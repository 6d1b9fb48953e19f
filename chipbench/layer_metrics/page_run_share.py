"""Share of the table pages the window's fused decode dispatches copied that
lay in a group copied as ONE: the growth of ``Engine.step_stats``'
``ctx_run_pages`` over that of ``ctx_pages`` (counted where the dispatch is
made, from the table array it built, with the kernel's own rule, group size
and alignment: ``ops/_page_copies.py``; on in the traced run only), all
replicas together, in per cent. A count. The kernels that walk their lanes'
tables themselves (the latent kernel, the sliding layers' window kernel)
start one copy for a group of pages whose pool ids are consecutive and one a
page for any other group, and what a copy costs them is starting it: near
100 says the free list handed the sequences whole stretches of the pool,
near 0 that every page is copied alone, as before the groups. None where the
program does not count them (a program from before the counters) or the
model's dispatches copied no page (a model that runs neither kernel)."""

KEYS = ("ctx_pages", "ctx_run_pages")


def read(run):
    pages = in_runs = 0
    for after, before in zip(run.step_after, run.step_before):
        if any(key not in after or key not in before for key in KEYS):
            return None  # a program that does not count them
        pages += after["ctx_pages"] - before["ctx_pages"]
        in_runs += after["ctx_run_pages"] - before["ctx_run_pages"]
    return 100.0 * in_runs / pages if pages else None
