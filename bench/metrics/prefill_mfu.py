"""Model step: the chunk (prompt) program's share of the chip's peak, in %.

Needed work (``work.prefill``) of every prompt the window admitted: its
layer matmuls, kept-block attention and selection, with the weights read
once per prompt.  The traced span holds some of the window's chunk steps:
their share of that work's least time, over their device time."""
from bench import work


def read(run):
    tr = run.trace or {}
    t = tr.get("program_s", {}).get("_chunk_fn")
    if not t:
        return None
    share = tr["program_runs"]["_chunk_fn"] / run.stats["chunks"]
    w = work.prefill(run.arch, run.geo_of,
                     [r.prompt_len for r in run.results])
    run.log(f"prefill_mfu: {tr['program_runs']['_chunk_fn']} of "
            f"{run.stats['chunks']} chunk steps traced, "
            f"{w.bound(run.peak)}-bound")
    return 100.0 * w.least_s(run.peak) * share / t
