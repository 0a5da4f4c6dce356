"""Share of the traced interval in which no XLA op ran on the device."""


def read(run):
    if run.reduced is None or not run.reduced.has_device:
        return None
    return run.reduced.idle_frac
