"""Mean time a high-priority request spends in the admission plane: the
ticket's submit-to-resolve latency less the engine's JCT of the task."""


def read(run):
    waits = [r["ticket_latency_s"] - r["jct_s"] for r in run.hi
             if r["ticket_latency_s"] is not None and r["jct_s"] is not None]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
