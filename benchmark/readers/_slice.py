"""Which requests the traced slice holds. A request that straddles an edge
counts by the share of its duration that lies inside, so a slice of whole
seconds over requests of any length gives an unbiased count."""


def shares(run) -> list:
    """[(record, share of it inside the slice)], shares above 0 only."""
    if run.slice is None:
        return []
    lo, hi = run.slice
    out = []
    for r in run.records:
        inside = min(r.end, hi) - max(r.start, lo)
        if inside > 0 and r.end > r.start:
            out.append((r, inside / (r.end - r.start)))
    return out
