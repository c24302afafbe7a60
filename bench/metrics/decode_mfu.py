"""Model step: the decode segment's share of the chip's peak, in %.

Needed work (``work.decode_steps``) of every decode step the window ran:
all weights once per step, the matmuls and kept-block attention of each
token at its real context, the K~ block scores and the one-row writes.
The traced span holds some of the window's segments: their share of that
work's least time, over their device time."""
from bench import work


def read(run):
    tr = run.trace or {}
    t = tr.get("program_s", {}).get("_segment_fn")
    if not t:
        return None
    steps = run.stats["segments"] * run.seg_len
    traced = tr["program_runs"]["_segment_fn"] * run.seg_len
    w = work.decode_steps(run.arch, run.geo_of(1),
                          work.decode_kv_lens(run.results), steps)
    run.log(f"decode_mfu: {traced} of {steps} steps traced, "
            f"{w.bound(run.peak)}-bound, {t / traced * 1e3:.3f} ms per step "
            f"on the device")
    return 100.0 * w.least_s(run.peak) * traced / steps / t
