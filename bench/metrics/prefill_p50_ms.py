"""Model step: 50th percentile over completed requests of admission to
first token, the first token's time minus the start of the request's
admission group (``RequestResult.admit_s``), in ms (host clock): staging,
the chunk bursts of its prompt, and the decode segments interleaved with
them.  A program that stamps admission with the first token
(``admit_s == first_token_s`` on every request) has no admission stamp
to read: no reading."""
from bench import stats


def read(run):
    ok = stats.ok(run.results)
    if all(r.admit_s == r.first_token_s for r in ok):
        return None
    run.log(f"prefill_p50_ms: {len(ok)} requests")
    return stats.percentile([r.first_token_s - r.admit_s for r in ok],
                            50) * 1e3
