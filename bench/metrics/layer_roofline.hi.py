"""The hi service's layer programs against their roofline, in %: over the
layer programs whose spans ``hi/<program>`` the trace holds, each
program's calls times the least time of one call (the larger of its
FLOPs over the peak and its bytes over the bandwidth, both from shapes),
summed, over their device time, summed. With one program, its least time
over its device time per call. Which bound applies to each program is
printed with the run's summary."""


def read(run):
    need = device = 0.0
    for program, (least, _bound) in run.least_time("hi").items():
        s = run.span(f"hi/{program}")
        if s is not None:
            need += s["calls"] * least
            device += s["device_s"]
    return 100.0 * need / device if device > 0 else None
