"""Mean, over the segments that started in the window (both services),
of the device thread's time after the segment's wait returned: the
engine's completion bookkeeping under its lock and the completion
callback (``released - end`` in the span log), less the segment's host
work, which the callback runs."""
from benchlib import spanlog


def read(run):
    w = spanlog.window(run)
    if w is None:
        return None

    def turn(s):
        hw = w.host_work.get((s.instance, s.seq))
        return s.released - s.end - (hw.end - hw.start if hw else 0.0)
    return spanlog.mean_ms(turn(s) for s in w.segments)
