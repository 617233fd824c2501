"""Share of the HBM roofline of the model step: the least bytes
(``chipbench/moe_counts.py``) of the waves whose step ran wholly inside
the traced part of the window, over HBM bandwidth times the device's busy
time there, summed over the chips.  Edge waves add busy time but no bytes,
so it reads low, never high."""


def read(facts):
    tr, waves = facts.get("trace"), facts.get("wave_bytes")
    if tr is None or not waves or tr.chips == 0:
        return None
    lo, hi = facts["traced"]
    least = sum(b for t0, t1, b in waves if t0 >= lo and t1 <= hi)
    busy = tr.busy_s * tr.chips
    if least <= 0 or busy <= 0:
        return None
    return 100.0 * least / (facts["peaks"].hbm_bytes * busy)
