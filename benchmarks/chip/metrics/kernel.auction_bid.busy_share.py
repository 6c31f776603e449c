"""Device time of the ``auction_bid`` kernel over device busy time. Its
Mosaic call is named after the jitted ``masked_row_top2_pallas`` that makes it."""

PATTERNS = ("masked_row_top2",)


def read(run):
    red = run.reduction
    if red is None or red.busy_s <= 0:
        return None
    t = red.kernel_s(*PATTERNS)
    return t / red.busy_s if t > 0 else None
