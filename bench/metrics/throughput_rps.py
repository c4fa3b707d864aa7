"""throughput_rps: requests answered per second of the window (host
clock).  A closed loop sends its in-flight requests ahead of their
answers, so its window closes when the time is up and all that was sent
is answered: every answer after the window opened counts, over the time
to the last one (``harness._served``)."""


def read(run):
    return run.served / run.served_s
