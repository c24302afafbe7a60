"""Kernel ``dsa_decode``: needed work over kept blocks of every decode
token (``work.decode_kernel``), the traced segments' share of its least
time over the summed device time of the Pallas kernels inside those
segment programs, in %."""
from bench import work


def read(run):
    tr = run.trace or {}
    t = tr.get("kernel_s", {}).get("_segment_fn")
    if not t:
        return None
    share = tr["program_runs"]["_segment_fn"] / run.stats["segments"]
    w = work.decode_kernel(run.arch, run.geo_of(1),
                           work.decode_kv_lens(run.results))
    run.log(f"dsa_decode_roofline: {w.bound(run.peak)}-bound, "
            f"{t:.6f} s of kernel time")
    return 100.0 * w.least_s(run.peak) * share / t
