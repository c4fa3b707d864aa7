"""setup_s: seconds from process start to the window's start (host clock).

Graph generation, the index build, service start and the warm-up of
every bucket shape, with compilation or cache loads; not the check."""


def read(run):
    return run.setup_s
