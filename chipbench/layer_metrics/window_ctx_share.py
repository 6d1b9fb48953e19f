"""The positions a sliding layer read of the window pool over the positions
a full layer read of the context pool, in the window's fused decode
dispatches: the growth of ``step_stats["window_ctx_tokens"]`` (the real
lanes' ``min(context, window)``, summed) over that of ``attn_ctx_tokens``
(their contexts, summed). A count. It says what the window saves a layer:
25 % where the mean context is four windows. None where the program does not
count them (a program from before the counter, a model without sliding
layers) or nothing was decoded."""

from chipbench import swa_counts


def read(run):
    counts = swa_counts.deltas(run)
    if counts is None or not counts["attn_ctx_tokens"]:
        return None
    if not getattr(run.model_cfg, "sliding_window", 0):
        return None
    return 100.0 * counts["window_ctx_tokens"] / counts["attn_ctx_tokens"]
