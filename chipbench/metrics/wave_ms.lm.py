"""Mean host time of one ``DecodeServer.step`` in the window (admit, the
wave, the argmax sync, emit and recycle)."""


def read(facts):
    s = facts.get("wave_s")
    if s is None or len(s) == 0:
        return None
    return 1e3 * float(s.mean())
