"""How late the load generator submitted: nearest-rank 99th percentile of
the engine's submit time minus the due time, in ms, over the requests due
in the traced interval."""
import numpy as np

from bench.stats import nearest_rank


def read(run):
    late = (run.submit - run.due)[run.traced_requests()]
    late = late[np.isfinite(late)]
    return 1e3 * nearest_rank(late, 99) if late.size else None
