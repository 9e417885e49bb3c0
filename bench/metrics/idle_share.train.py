"""idle_share.train: 1 - the union of device-operation intervals over the
traced window, averaged over the chips, in percent (bench/trace.py)."""


def read(facts):
    t = facts["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
