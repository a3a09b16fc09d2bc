"""Process start to the first step of the window: imports, building the
inputs, initialising on the device, compiling (or reading the compile
cache) and the warm-up steps (host clock)."""


def read(r):
    return r.setup_s
