"""Output tokens delivered in the window over the window's wall time
(host clock; the window closes at the first step boundary after
``--seconds``)."""


def read(run):
    w = run.window
    return sum(d.k for d in w.deliveries) / w.seconds
