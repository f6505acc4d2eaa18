"""itl_p95_ms: 95th percentile of the gaps between consecutive output
tokens of one request, over all gaps of all attempted requests (host
clock)."""

from bench import stats


def read(view):
    gaps = stats.itl(view.run)
    return 1e3 * stats.percentile(gaps, 95) if gaps.size else None
