"""Device self time of DECOMPOSE's matcher over device busy time: the
operations the program's stage scopes place under its matcher call
(``spectra.decompose.match`` and the bidding rounds in it), the
``auction_bid`` kernel included."""

from chipbench import scopes


def read(run):
    red = run.reduction
    if red is None or red.busy_s <= 0:
        return None
    by_scope = scopes.scope_seconds(run)
    if by_scope is None:
        return None
    t = sum(by_scope.get(s, 0.0) for s in scopes.MATCH_SCOPES)
    return t / red.busy_s if t > 0 else None
