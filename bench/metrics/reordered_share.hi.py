"""Share of the window's high-priority segments that the device queue took
ahead of an equal-priority request launched earlier (``ahead`` > 0), in %.
None where the program's segment spans have no ``ahead`` field."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None
    segs = [s for s in w.segments if s.priority == w.hi]
    if not segs or not hasattr(segs[0], "ahead"):
        return None
    return 100.0 * sum(s.ahead > 0 for s in segs) / len(segs)
