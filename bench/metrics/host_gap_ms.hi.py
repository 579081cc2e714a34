"""Mean, over high-priority requests, of the summed host hop between
segments: the next segment's submission less the previous one's end."""


def read(run):
    per_req = [sum(nxt[1] - prev[3] for prev, nxt in zip(segs, segs[1:]))
               for segs in run.hi_segments if len(segs) > 1]
    if not per_req:
        return None
    return 1e3 * sum(per_req) / len(per_req)
