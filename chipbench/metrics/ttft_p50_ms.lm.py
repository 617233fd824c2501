"""Median time from submit to first token, over the requests whose first
token came inside the window (in a traced run, not those whose wait spans
the profiler's stop)."""
import numpy as np


def read(facts):
    t = facts.get("ttft_s")
    if t is None or len(t) == 0:
        return None
    return 1e3 * float(np.median(t))
