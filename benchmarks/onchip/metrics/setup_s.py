"""Set-up: from the moment JAX has its devices (or, in a multi-seed run,
the seed's start) to the first timed call: the program's import, the
cell's inputs, and the warm-up that compiles or fetches every shape the
window uses."""


def read(run):
    return run.setup_s
