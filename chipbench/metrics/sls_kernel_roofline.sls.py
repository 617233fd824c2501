"""Share of the HBM roofline of the SLS kernel: the least bytes of the
batches that ran wholly inside the traced part of the window, over HBM
bandwidth times the device time of the kernel's events, summed over the
chips.  Edge batches add kernel time but no bytes, so it reads low, never
high."""
import re

KERNEL = re.compile(r"sls", re.I)


def read(facts):
    tr, b = facts.get("trace"), facts.get("batches")
    if tr is None or not b:
        return None
    lo, hi = facts["traced"]
    least = sum(x[3] for x in b if x[0] >= lo and x[1] <= hi)
    kernel_s = tr.time_of(KERNEL)
    if least <= 0 or kernel_s <= 0:
        return None
    return 100.0 * least / (facts["peaks"].hbm_bytes * kernel_s)
