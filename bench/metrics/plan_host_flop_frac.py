"""Share of the model operations that the plan puts on the host, weighted
by the requests due in the traced interval (from the plan and the shapes)."""


def read(run):
    host = total = 0.0
    for m in run.tenant[run.traced_requests()]:
        flops = [c.flops for c in run.costs[m]]
        host += sum(flops[run.partition(m):])
        total += sum(flops)
    return host / total if total else None
