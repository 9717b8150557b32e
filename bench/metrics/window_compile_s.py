"""Seconds JAX spent compiling, or loading from the persistent compile
cache, inside the measured window (its monitoring events, summed over
threads). Every kernel shape loads in set-up; what comes here is state
that the program builds on the device at a size that changes with each
new candidate batch. A cell's first run in a checkout compiles it, and
later runs load it from the cache."""


def read(run):
    return run.compile_s
