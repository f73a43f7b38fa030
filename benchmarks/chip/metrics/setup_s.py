"""Seconds from process start to the window's opening: imports, weights
made on the device, compiling or loading every program from the cache,
the warm-up shapes and the generator's warm requests."""


def read(run):
    return run.setup_s
