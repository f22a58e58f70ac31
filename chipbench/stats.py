"""Statistics shared by the latency readers."""
import math


def percentile(values, q: float):
    """The smallest value with at least ``q`` percent of values at or
    below it; None without values."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]
