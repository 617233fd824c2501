"""95th percentile, over every batch completed inside the window, of the
time from its ``submit`` call to its outputs being ready."""
import numpy as np


def read(facts):
    b = facts.get("batches")
    if not b:
        return None
    return 1e3 * float(np.percentile([tr - ts for ts, tr, *_ in b], 95))
