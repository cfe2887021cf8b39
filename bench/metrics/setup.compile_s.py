"""Set-up: seconds of backend compiles and compile-cache loads over every
program of the run, from the program's compile counter (the window
compiles nothing, so these are set-up's)."""
from bench.program import compile_s


def read(r):
    return compile_s()
