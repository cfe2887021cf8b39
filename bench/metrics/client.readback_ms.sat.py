"""Replies to the client: per burst, from the outputs of ``run_waves``
being ready to their being on the host (dequeued records included), mean
over the window's bursts (traced run)."""


def read(r):
    return r.run.span_ms("readback")
