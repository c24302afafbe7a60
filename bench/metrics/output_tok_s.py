"""Output tokens of every completed request over the whole window, from
the opening of the window to the last retirement, in tokens per second
(host clock)."""
from bench import stats


def read(run):
    ok = stats.ok(run.results)
    n = sum(len(r.tokens) for r in ok)
    run.log(f"output_tok_s: {n} tokens of {len(ok)} requests in "
            f"{run.window_s:.3f} s")
    return n / run.window_s
