"""Share of the singleton candidates the swap walk visited whose batched
gain/preference row was thrown away and re-derived, because a vertex of
their 1-hop neighbourhood had moved: 100 x the sum of ``stale_rows`` over
the sum of ``singles_visited`` of ``swap.walk`` spans inside the window,
in %."""


def read(view):
    walks = [s["attrs"] for s in view.spans("swap.walk")]
    singles = sum(a["singles_visited"] for a in walks)
    if not singles:
        return None
    return 100.0 * sum(a["stale_rows"] for a in walks) / singles
