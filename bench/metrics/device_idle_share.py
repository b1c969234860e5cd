"""Device: share of the traced window in which no operation ran on the
device, 1 - busy / window, mean over the chips (%)."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
