"""The longest single hold of the serving loop's host in the run, s: the
longest ``dispatch`` span (the dispatcher's call) or ``launch`` span (the
enqueues of E, D and C), in which the host holds back the queue while the
device's work is not what it waits on.  The image's ``copy`` is left out:
its span holds the wait for the device as well as the transfer."""


def read(run):
    d = run.spans.durations("dispatch") + run.spans.durations("launch")
    return max(d) if d else None
