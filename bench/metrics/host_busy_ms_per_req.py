"""Host time spent executing XLA:CPU programs (the plan's suffixes) in the
traced interval, per request with a host suffix due in it, in ms: the
union of each PjRt CPU client thread's traced events, summed over those
threads."""
from bench import tracing


def read(run):
    if run.events is None:
        return None
    n = sum(1 for m in run.tenant[run.traced_requests()]
            if run.partition(m) < run.n_stages(m))
    busy = tracing.host_xla_cpu_busy_s(run.events)
    return 1e3 * busy / n if n and busy > 0 else None
