"""Activation bytes that cross from the accelerator to the host at the
plan's cut, per request due in the traced interval, in KB (1000 bytes).
A tenant whose plan runs all or none of its stages on the accelerator
sends nothing across."""


def read(run):
    mask = run.traced_requests()
    if not mask.any():
        return None
    total = 0.0
    for m in run.tenant[mask]:
        p = run.partition(m)
        if 0 < p < run.n_stages(m):
            total += run.costs[m][p - 1].out_bytes
    return total / 1e3 / int(mask.sum())
