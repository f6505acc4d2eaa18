"""ttft_p50_s: median of due time to first output token over every
attempted request (host clock; a request with no token by the drain cap
counts as infinitely late)."""

from bench import stats


def read(view):
    return stats.percentile(stats.ttft(view.run), 50)
