"""Host staging: per burst, from the call that places the op arrays on
the mesh (``_ElasticBase._place``) until they are ready on the device,
mean over the window's bursts (traced run)."""


def read(r):
    return r.run.span_ms("place")
