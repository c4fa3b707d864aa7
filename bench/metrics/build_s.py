"""build_s: host-clock seconds of ``serve(h, ...)`` until the engine
reports its index built: HL-index construction, or the (max, min)
closure, inside set-up.  Moves setup_s."""


def read(run):
    return run.build_s
