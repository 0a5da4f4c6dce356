"""Share of their roofline that the stage programs reach on the device, in
percent: for every stage program that started in the traced interval, the
larger of its operations over peak FLOP/s and its bytes (input, weights,
output) over HBM bandwidth, summed, over the summed device time of those
executions.  Nothing to read where the executions cannot be attributed to
stages or no peak is known."""


def read(run):
    if not run.stage_execs or run.peak is None:
        return None
    least = sum(run.costs[m][s].roofline_s(run.peak) for m, s, _, _ in run.stage_execs)
    took = sum(e - s for _, _, s, e in run.stage_execs) * 1e-9
    return 100.0 * least / took if took > 0 else None
