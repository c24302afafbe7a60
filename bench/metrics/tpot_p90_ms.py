"""90th percentile over completed requests of (finish - first token) /
(tokens - 1), in ms (host clock)."""
from bench import stats


def read(run):
    v = stats.tpot_s(run.results)
    run.log(f"tpot_p90_ms: {len(v)} requests")
    p = stats.percentile(v, 90)
    return None if p is None else p * 1e3
