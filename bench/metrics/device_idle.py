"""Share of the traced window, in %, in which the device ran nothing:
1 - (union of the device's operation intervals) / (window length)."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    win = tr.window()
    length = win[1] - win[0]
    if length <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(win) / length)
