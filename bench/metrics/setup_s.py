"""Set-up seconds: process start to the end of the warm-up windows
(imports, JAX start, the arrays' generation, the daemon, the compiles or
cache loads of every shape the cell uses)."""


def read(run):
    return run.setup_s
