"""How late the open-loop generator sent its requests: the 95th
percentile of (time sent - time due) over the requests due in the
window, in ms.  A late generator offers less load than the cell states."""

from bench import stats


def read(r):
    lags = sorted(q.sent_t - q.due_t for q in r.requests
                  if q.sent_t is not None)
    return 1e3 * stats.nearest_rank(lags, 95) if lags else None
