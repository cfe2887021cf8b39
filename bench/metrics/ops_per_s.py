"""Operations answered in the window (acknowledged enqueues plus answered
dequeues, every op of a completed burst) over the window's seconds."""


def read(r):
    w = r.window
    return w["answered"] / w["seconds"] if w["seconds"] > 0 else None
