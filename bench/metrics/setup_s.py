"""Process start to window start: imports, the chip, weights made from the
seed, the engine and pool, and the warm-up that compiles (or loads from the
compile cache) every shape the window uses."""


def read(run):
    return run.setup_s
