"""Mean, over high-priority requests, of the summed wait of their
segments between submission to the engine and the start of their run on
the device thread (``ExecRecord.start - KernelRequest.submit_time``)."""


def read(run):
    per_req = [sum(start - submit for _, submit, start, _, _ in segs)
               for segs in run.hi_segments]
    if not per_req:
        return None
    return 1e3 * sum(per_req) / len(per_req)
