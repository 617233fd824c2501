"""Share of the chips' peak of the whole pooled step: the least time the
cell's chips could take for the window's batches (FLOPs over the FLOP
peak or least bytes over HBM bandwidth, whichever is longer), over the
window without the profiler's stop in a traced run.  A lookup step is
bound by bytes."""


def read(facts):
    if "least_time_s" not in facts or not facts.get("batches"):
        return None
    return 100.0 * facts["least_time_s"] / facts["measured_s"]
