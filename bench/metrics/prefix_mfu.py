"""Whole-step share of the chip's peak, in percent: the operations of the
stage programs that started on the device in the traced interval, over the
interval's length times the peak FLOP/s."""


def read(run):
    if not run.stage_execs or run.peak is None or run.reduced is None:
        return None
    flops = sum(run.costs[m][s].flops for m, s, _, _ in run.stage_execs)
    return 100.0 * flops / (run.reduced.window_s * run.peak["flops"])
