"""Process start to the window's start: imports, native build, data
generation, file write, first action with its compile or cache load,
warm-up. The reference and the comparison come after the window and are
not in it."""


def read(run):
    return run.setup_s
