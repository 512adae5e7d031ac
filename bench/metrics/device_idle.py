"""Device: the share of the traced window in which no executable ran on
the chip, in % (1 - union of ``XLA Modules`` intervals / window)."""


def read(rec):
    t = rec["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
