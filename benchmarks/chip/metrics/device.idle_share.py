"""Share of the traced window in which no operation ran on the device."""


def read(run):
    red = run.reduction
    if red is None or red.window_s <= 0:
        return None
    return red.idle_share
