"""1 - busy/window over the traced window, averaged over the chips."""


def read(run, params):
    t = run.get("trace")
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
