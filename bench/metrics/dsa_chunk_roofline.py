"""Kernel ``dsa_chunk_prefill``: needed work over the kept block pairs of
every prompt (``work.chunk_kernel``), the traced chunk steps' share of its
least time over the summed device time of the Pallas kernels inside those
chunk programs, in %."""
from bench import work


def read(run):
    tr = run.trace or {}
    t = tr.get("kernel_s", {}).get("_chunk_fn")
    if not t:
        return None
    share = tr["program_runs"]["_chunk_fn"] / run.stats["chunks"]
    w = work.chunk_kernel(run.arch, run.geo_of,
                          [r.prompt_len for r in run.results])
    run.log(f"dsa_chunk_roofline: {w.bound(run.peak)}-bound, {t:.6f} s of "
            f"kernel time")
    return 100.0 * w.least_s(run.peak) * share / t
