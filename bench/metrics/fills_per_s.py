"""Gap fills the FIKIT scheduler made per second of the window (the
engine's ``fill_count`` across the window)."""


def read(run):
    return run.fills / run.seconds
