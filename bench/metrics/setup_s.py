"""Set-up: process start to the window's opening — imports, building the
engine (compiling, or loading programs from the cache), drawing the
weights, warming every shape, and any requests the mix submits first."""


def read(run):
    return run.setup_s
