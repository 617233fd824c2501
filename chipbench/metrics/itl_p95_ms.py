"""95th percentile over every gap between two consecutive tokens of one
request, both emitted inside the window."""
import numpy as np


def read(facts):
    g = facts.get("itl_s")
    if g is None or len(g) == 0:
        return None
    return 1e3 * float(np.percentile(g, 95))
