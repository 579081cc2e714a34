"""The hi service's layer program against its roofline, in %: the least
time of one call (the larger of its FLOPs over the peak and its bytes
over the bandwidth, both from shapes) over its device time per call in
the trace. Which bound applies is printed with the run's summary."""


def read(run):
    s = run.span("hi/layer")
    if s is None:
        return None
    least, _bound = run.least_time("hi")
    return 100.0 * least / (s["device_s"] / s["calls"])
