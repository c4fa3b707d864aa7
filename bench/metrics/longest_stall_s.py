"""longest_stall_s: the longest the benchmark's process stood still in
the window (host clock): how late a thread that sleeps 5 ms at a time
woke at worst.  The garbage collector, a thread that holds the
interpreter, and the machine pausing the process all show here; a tail
that moves with it is the host's, not the service's."""


def read(run):
    return run.longest_stall_s
