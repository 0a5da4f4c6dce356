"""The largest of the tenants' nearest-rank 95th percentiles of latency, in
ms.  Every tenant is a customer; under Zipf popularity the pooled tail can
hide the least popular ones."""
import numpy as np

from bench.stats import nearest_rank


def read(run):
    lat = run.latency_s
    return 1e3 * max(nearest_rank(lat[run.tenant == m], 95)
                     for m in np.unique(run.tenant))
