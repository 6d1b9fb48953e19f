"""Share of requests that landed on a replica already holding their group's
prefix: the response shows cached_prompt_tokens >= the prefix less one
page. A count."""


def read(run):
    shared = [r for r in run.good if r["prefix_len"]]
    if not shared:
        return None
    warm = sum(
        r["body"]["usage"]["cached_prompt_tokens"]
        >= r["prefix_len"] - run.page
        for r in shared
    )
    return 100.0 * warm / len(shared)
