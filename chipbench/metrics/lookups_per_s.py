"""Lookups pooled in the batches completed inside the window, over the
window's length."""


def read(facts):
    if "batches" not in facts:
        return None
    return sum(b[2] for b in facts["batches"]) / facts["window_s"]
