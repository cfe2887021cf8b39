"""Wave program: per burst, the device self time of the wave phase
``commit`` (each instant of busy time goes to the innermost op running,
and the op to the phase of its instruction in the program's compiled
text), averaged over the chips (traced run)."""
from bench.program import wave_phase_ms


def read(r):
    return wave_phase_ms(r, "commit")
