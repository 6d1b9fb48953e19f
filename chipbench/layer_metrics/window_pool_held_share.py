"""Window pages a sequence holds or a hit may still take (``/stats``'
``window_pages_held``: the pool's, less the free and the given back) over
the window pool's pages at the close of the window, fullest replica. The
documents' last windows and the lanes' own turns are about three quarters of
``longdocs``' pool; near 100 the giving back has stopped working (every
page is held or kept) and the next request's pages come out of a document's
last window. None where the program does not report the keys or the model
has no window pool."""


def read(run):
    shares = []
    for stats in run.stats_after:
        pages, held = stats.get("window_pages"), stats.get("window_pages_held")
        if not pages or held is None:
            return None
        shares.append(100.0 * held / pages)
    return max(shares) if shares else None
