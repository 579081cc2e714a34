"""Mean, over high-priority requests, of the device-thread time their
segments spent, between being launched onto the device queue and
starting, on the turns of other high-priority requests' segments."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None
    return spanlog.mean_ms(sum(w.waits_behind(s)[0] for s in segs)
                           for segs in w.requests.values())
