"""Mean, over the segments that started in the window (both services),
of the time from the device thread's start of the segment to the return
of its jitted call (``dispatched - start`` in the span log)."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None
    return spanlog.mean_ms(s.dispatched - s.start for s in w.segments)
