"""The same clock as setup.build_s inside the window. It should read 0:
anything else is a program that was not warmed up or is traced anew for
every action."""


def read(run):
    return run.window_build_s
