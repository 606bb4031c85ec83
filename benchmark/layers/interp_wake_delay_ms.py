"""Dispatcher: median milliseconds by which a thread of the dispatcher's process
that slept 20 ms ran Python again after its timer (the window's
INTERP_WAKE_DELAY records, fifty a second while the process traces:
`Server._probe_interpreter`): the kernel's timer and scheduler, then the wait
for the interpreter lock that the dispatcher shares with every other thread of
its process. The median, because one or two stalls of 30-50 ms a window carry
the mean; that, the p95 and the longest wake are in the `interp_wake_delay`
line. Found (PR 52): the host's own timer lateness, 0.24 ms in an in-process
cell whose dispatcher is parked four fifths of the time, is most of every
reading, and the saturated served cells read it to within 0.1 ms: nobody holds
the interpreter for milliseconds, so this bounds what the lock can add to
`op_p95_ms` and does not explain it. None on a program without the probe."""

from benchmark import remote_timeline

SOURCE = "program_span"


def read(run):
    found = remote_timeline.probe(run)
    return found and found["median_ms"]
