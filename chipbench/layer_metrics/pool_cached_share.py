"""Pages holding cached blocks over the pool's pages at the close of the
window, fullest replica. Under 100 the pool never wrapped, so no shared
prefix was evicted; at 100 the hit share and the shape set are at risk."""


def read(run):
    shares = []
    for pod in run.pods:
        bm = pod.engine.block_manager
        shares.append(100.0 * bm.num_cached_pages / bm.config.total_pages)
    return max(shares)
