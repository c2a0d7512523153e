"""Small statistics, copied from tools/serve_bench.py (``_percentile``)."""


def percentile(values, q):
    """Linear-interpolated percentile of a non-empty list, q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return percentile(values, 50.0)
