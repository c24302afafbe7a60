"""Scheduler: 50th percentile over completed requests of the queue wait,
the start of the request's admission group (``RequestResult.admit_s``)
minus its scheduled arrival, in ms (host clock).  A program that stamps
admission with the first token (``admit_s == first_token_s`` on every
request) has no admission stamp to read: no reading."""
from bench import stats


def read(run):
    ok = stats.ok(run.results)
    if all(r.admit_s == r.first_token_s for r in ok):
        return None
    run.log(f"queue_wait_p50_ms: {len(ok)} requests")
    return stats.percentile([r.admit_s - r.arrival_s for r in ok], 50) * 1e3
