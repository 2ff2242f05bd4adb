"""Seconds before the window in which some thread traced, lowered,
compiled or loaded a program (engine/compile_clock.py: the union of jax's
own build windows)."""


def read(run):
    return run.setup_build_s
