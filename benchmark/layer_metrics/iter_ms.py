"""``iter_ms`` (layer: serving scheduler + decode step): window
milliseconds over decode iterations in the window.  Prefills are inside
it on purpose: that is what a running session waits through."""


def read(facts):
    if not facts.get("window_iterations"):
        return None
    return facts["window_s"] * 1e3 / facts["window_iterations"]
