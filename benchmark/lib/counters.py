"""Arithmetic shared by the readers of cumulative host counters."""


def share_pct(before, now, window_s: float, parts: int = 1):
    """100 * (now - before) / (window x parts); None where either reading
    is missing. `parts` is the number of threads whose time was summed."""
    if before is None or now is None or window_s <= 0 or parts <= 0:
        return None
    return 100.0 * (now - before) / (window_s * parts)
