"""What of a full layer's decode table is context: the growth of
``step_stats["attn_ctx_tokens"]`` (the real lanes' contexts, summed over the
window's fused decode steps) over that of ``decode_table_slots`` (the token
slots those lanes' block tables had room for: lanes x the dispatch's table
width x the page, the same steps). A count. Every lane of a dispatch gets a
table as wide as the longest lane's bucket and the full layers' decode kernel
runs a program a (lane, table page), so 100 % less this share is what short
lanes pay for sharing a dispatch with a long one. None where the program
does not count the tables (a program from before the counter) or nothing was
decoded."""

from chipbench import prerouted_counts


def read(run):
    counts = prerouted_counts.table_deltas(run)
    if counts is None or not counts["decode_table_slots"]:
        return None
    return 100.0 * counts["attn_ctx_tokens"] / counts["decode_table_slots"]
