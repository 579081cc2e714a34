"""Share of the gaps the scheduler opened in the window into which it
launched at least one fill, in %."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None or not w.gaps:
        return None
    return 100.0 * sum(g.fills > 0 for g in w.gaps) / len(w.gaps)
