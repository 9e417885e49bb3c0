"""collective_exposed_share.train: time in which collectives ran on a
chip and nothing else did, over the traced window, averaged over the
chips, in percent (bench/trace.py). Nothing to read on one chip."""


def read(facts):
    t = facts["trace"]
    if facts["chips"] < 2 or not t["window_s"]:
        return None
    return 100.0 * t["exposed_collective_s"] / t["window_s"]
