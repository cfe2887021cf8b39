"""Per membership change of the window, from its issue to the reply of
the dispatch that answers the last op due before the change returned:
the pause, and the drain of what came due during it; mean over the
window's changes, on the host clock."""


def read(r):
    done = r.run.plan.recovered()
    if not done:
        return None
    return sum(c.recovered - c.issued for c in done) / len(done)
