"""Mean host time of ``ProgramExecutor.submit`` in the window: harden,
marshal, transfer and dispatch of one batch (with a wait for the oldest
batch when the executor's own depth is full)."""


def read(facts):
    s = facts.get("submit_s")
    if s is None or len(s) == 0:
        return None
    return 1e3 * float(s.mean())
