"""Percentile arithmetic with the miss rule, and sample counts.

A request that failed or did not finish is a MISS: it is placed above every
finished one, so it pushes the tail up instead of vanishing from it. A
percentile is always one of the samples (nearest rank, rounded up) — with
a few hundred samples an interpolated tail is a number nobody observed."""
import math


def percentile(values, q: float, n_miss: int = 0, miss_value: float = math.inf):
    """The q-th percentile (0 < q <= 100) of ``values`` plus ``n_miss``
    samples that rank above all of them and read as ``miss_value``."""
    vals = sorted(float(v) for v in values)
    n = len(vals) + n_miss
    if n == 0:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q={q} outside (0, 100]")
    rank = max(1, math.ceil(q / 100.0 * n))          # 1-based nearest rank
    if rank <= len(vals):
        return vals[rank - 1]
    return float(miss_value)


def samples_beyond(n: int, q: float) -> int:
    """How many samples rank strictly above the q-th percentile."""
    return n - max(1, math.ceil(q / 100.0 * n))
