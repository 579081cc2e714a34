"""The collector's pauses that began in the window, in ms per second of
the window."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None
    paused = sum(c.end - c.start for c in w.collections)
    return 1e3 * paused / w.seconds
