"""tok_per_s: prompt and output tokens served inside the window, per
second (host clock).  Output tokens count when emitted; a request's
prompt tokens count in proportion to the part of its prefill (from the
step call that put it on a lane to its first token) that lies inside
the window, whatever way the engine prefills."""

from bench import stats


def read(view):
    return stats.tok_per_s(view.run)
