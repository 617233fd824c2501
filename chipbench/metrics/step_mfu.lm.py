"""Share of the chips' FLOP peak of the served work: the FLOPs of every
token the window's waves fed (prompt and output; matmuls and attention
over each token's live context), over the window's length (without the
profiler's stop in a traced run) times the chips times the peak."""


def read(facts):
    if "flops" not in facts:
        return None
    pk = facts["peaks"]
    return 100.0 * facts["flops"] / (facts["measured_s"] * facts["chips"]
                                     * pk.flops)
