"""50th percentile over completed requests of first-token time minus the
scheduled arrival, in ms (host clock)."""
from bench import stats


def read(run):
    v = stats.ttft_s(run.results)
    run.log(f"ttft_p50_ms: {len(v)} requests")
    p = stats.percentile(v, 50)
    return None if p is None else p * 1e3
