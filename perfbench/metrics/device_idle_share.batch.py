"""Share of the traced window in which no operation ran on the chip."""


def read(run):
    tr = run["trace"]
    if not tr["devices"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
