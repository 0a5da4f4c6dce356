"""Device busy time (union of the device's XLA ops) in the traced interval,
per request with an accelerator prefix due in it, in ms."""


def read(run):
    if run.reduced is None or not run.reduced.has_device:
        return None
    n = sum(1 for m in run.tenant[run.traced_requests()] if run.partition(m) > 0)
    return 1e3 * run.reduced.busy_s / n if n else None
