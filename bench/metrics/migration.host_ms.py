"""Migration, host side: per membership change in the traced window, the
program's ``migration:stage`` and ``migration:land`` spans (the store
copied to the host and put back on the other mesh), summed; mean over
the changes."""
from bench.program import CHANGE_SPANS, window_spans


def read(r):
    changes = window_spans(r, CHANGE_SPANS)
    if not changes:
        return None
    host = window_spans(r, ("migration:stage", "migration:land"))
    return sum(e - s for s, e, _ in host) / len(changes) * 1e-6
