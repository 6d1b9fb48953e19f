"""Prefix hits the window pool cut back over the requests the window
admitted: the growth of ``/stats``' ``window_short_hits`` (admissions whose
cached prefix in the context pool was longer than the longest one whose last
window the window pool still held whole) over the window's responses and the
requests in flight at its close, every one of which asks of a resident
document. 0 in ``longdocs``: anything else says the cell lost a document's
last window and prefilled its tokens again. What it cannot see: a scorer
that sent the request here for a prefix whose window is gone counts it as a
hit all the same. None where the program does not report the count."""

from chipbench import swa_counts


def read(run):
    counts = swa_counts.pool_deltas(run)
    admitted = len(run.good) + len(run.failed) + len(run.in_flight)
    if counts is None or not admitted:
        return None
    if not any(s.get("window_pages") for s in run.stats_after):
        return None
    return 100.0 * counts["window_short_hits"] / admitted
