"""Mean, over the high-priority tickets that arrived in the window, of
their wait in the admission queue: the pop of their group less their
arrival."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None
    popped = [a for a in w.admissions if a.popped is not None]
    hi = min((a.priority for a in popped), default=None)
    return spanlog.mean_ms(a.popped - a.arrival for a in popped
                           if a.priority == hi)
