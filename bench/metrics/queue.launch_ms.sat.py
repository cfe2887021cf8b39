"""Wave program, host side: per burst, the program's ``queue:launch``
span (placing the op arrays, the runtime's burst hook and the enqueue of
the wave program), mean over the traced window's bursts."""
from bench.program import span_ms


def read(r):
    return span_ms(r, "queue:launch")
