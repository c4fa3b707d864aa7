"""queue_wait_ms_mean: mean milliseconds a request waited in the
service's queue before a take selected it, over the traced stretch.
The service adds each taken request's wait to ``ServiceStats``'s
``queue_wait_s`` and counts it in ``queued``; each take carries its
share as the ``wait_s`` and ``taken`` metadata of its
``repro.serve.take`` span, and those of the takes in the stretch are
summed here.  None for a program without the span."""
import spans


def read(run):
    att = spans.of_run(run)
    if att is None or not att.taken:
        return None
    return att.queue_wait_s / att.taken * 1e3
