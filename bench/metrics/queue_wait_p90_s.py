"""queue_wait_p90_s: 90th percentile of due time to first appearance on
a lane, over the attempted requests (host clock).  Layer: admission
(serve/scheduler.RequestScheduler).  Moves ttft_p90_s."""

from bench import stats


def read(view):
    return stats.percentile(stats.queue_wait(view.run), 90)
