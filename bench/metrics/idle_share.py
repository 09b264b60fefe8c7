"""idle_share: the share of the traced window in which no operation ran on
the device (device layer): 1 − busy ÷ window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_ns"] or not tr["busy_ns"]:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
