"""Mean, over high-priority requests, of their segments' summed host work
(the head's sampling) in the span log."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None

    def work(segs):
        hws = (w.host_work.get((s.instance, s.seq)) for s in segs)
        return sum(h.end - h.start for h in hws if h is not None)
    return spanlog.mean_ms(work(v) for v in w.requests.values())
