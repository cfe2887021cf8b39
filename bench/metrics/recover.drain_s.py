"""Drain after a change: per membership change of the window, from the
program's return from the change to the reply that answers the last op
due before it returned, on the host clock; mean over the changes."""


def read(r):
    done = r.run.plan.recovered()
    if not done:
        return None
    return sum(c.recovered - c.returned for c in done) / len(done)
