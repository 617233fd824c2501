"""Output tokens whose emit stamp falls inside the window, over the
window's length."""


def read(facts):
    if "token_stamps" not in facts:
        return None
    return len(facts["token_stamps"]) / facts["window_s"]
