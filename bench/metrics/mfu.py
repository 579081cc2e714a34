"""Model FLOPs of every request completed in the window (both services,
counted from shapes) over the window's seconds times the chip's peak
bfloat16 FLOP/s, in %."""


def read(run):
    done = (run.hi_done_in_window * run.request_flops("hi")
            + run.lo_done * run.request_flops("lo"))
    if done <= 0:
        return None
    return 100.0 * done / (run.seconds * run.peak["bf16_flops_per_s"])
