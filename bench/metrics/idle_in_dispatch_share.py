"""idle_in_dispatch_share: percent of the traced stretch in which no
operation ran on the device while the service thread was in neither
``repro.serve.wait`` (queue empty) nor ``repro.serve.linger`` (the
coalescing wait): device idle time spent in the host's dispatch work.
``device_idle_share`` minus this share is the idle time spent waiting
for work.  None for a program without service spans."""
import spans


def read(run):
    att = spans.of_run(run)
    if att is None:
        return None
    return 100.0 * att.idle_in_dispatch_s / att.window_s
