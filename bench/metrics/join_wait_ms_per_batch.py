"""join_wait_ms_per_batch: wall milliseconds of the service thread's
``repro.serve.join`` spans per span: from the device call through the
fetch of its answers (launch, the device's run, the copy back), per
kind group.  Beside ``join_ms_per_batch`` (device time of the same
calls) it shows the launch and fetch overhead.  Inside the traced
stretch; a span across its edge counts by its share inside.  None for a
program without the span."""
import spans


def read(run):
    att = spans.of_run(run)
    if att is None or not att.count(spans.JOIN):
        return None
    return att.wall_s(spans.JOIN) / att.count(spans.JOIN) * 1e3
