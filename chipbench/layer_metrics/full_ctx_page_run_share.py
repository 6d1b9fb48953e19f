"""Share of the table pages the full layers' ``paged_attention`` calls of the
window's fused decode dispatches copied that lay in a group copied as ONE:
the growth of ``Engine.step_stats``' ``full_ctx_run_pages`` over that of
``full_ctx_pages`` (counted where the dispatch is made, from the block table
array it built, with the kernel's own rule, group size and alignment, from the
table's first page: ``ops/_page_copies.py``; on in the traced run only), all
replicas together, in per cent. A count. Since PR 57 a full layer's call walks
a lane's own pages and starts one copy for a group of pages whose pool ids are
consecutive and one a page for any other group, as the latent and the window
call do (``page_run_share``, which goes on reading those two). None where the
program does not count them (a program from before PR 57, whose full call was
a program a table page) or the dispatches copied no page (a latent pool)."""

KEYS = ("full_ctx_pages", "full_ctx_run_pages")


def read(run):
    pages = in_runs = 0
    for after, before in zip(run.step_after, run.step_before):
        if any(key not in after or key not in before for key in KEYS):
            return None  # a program that does not count them
        pages += after[KEYS[0]] - before[KEYS[0]]
        in_runs += after[KEYS[1]] - before[KEYS[1]]
    return 100.0 * in_runs / pages if pages else None
