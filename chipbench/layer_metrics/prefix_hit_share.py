"""Sum of cached_prompt_tokens over sum of prompt_tokens of the window's
responses. A count. ``prefix_hit_share.bypass`` is the same count in a cell whose
prompts share nothing (predicted 0): a change to the prefix path must leave
that cell alone."""


def read(run, suffix=""):
    prompt = sum(r["body"]["usage"]["prompt_tokens"] for r in run.good)
    if not prompt:
        return None
    cached = sum(r["body"]["usage"]["cached_prompt_tokens"] for r in run.good)
    return 100.0 * cached / prompt
