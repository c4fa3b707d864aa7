"""device_idle_share: percent of the traced stretch in which no
operation ran on the device (1 - union of operation intervals / stretch,
averaged over the chips)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
