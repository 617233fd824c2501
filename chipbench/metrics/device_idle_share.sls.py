"""Share of the traced window in which no operation ran on the device
(mean over the chips), from the profiler trace."""


def read(facts):
    tr = facts.get("trace")
    if tr is None or tr.window_s <= 0 or tr.chips == 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
