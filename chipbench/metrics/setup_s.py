"""Set-up seconds: from process start to the window's opening (loading,
weights, traffic, compiling or loading compiled programs, warm-up)."""


def read(facts):
    return facts.get("setup_s")
