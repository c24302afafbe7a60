"""Readings that set a cell's correctness limits: the program's sound
runs and the lower-precision control, many seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...
        [--root <tree>]

For each seed: weights and requests from the seed, planted as the cell
says, one ``serve`` of the cell's traffic for ``--seconds`` through one
engine (built and warmed once; only the weights change between seeds),
then on the same sampled requests (``check.sample``) the compared numbers
of the served tokens against the float32 reference (``sound``), and of the
tokens an fp8 reference puts first at each position of the same prompts
and served tokens (``control``, the configuration's reference's
``served_logits(precision="fp8")``).  One JSON line per seed,
with the seconds each reading took.  The benchmark's own runs never run
this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run


def readings(c, eng, w, seed: int, seconds: float) -> dict:
    """Serve one seed's requests on ``eng`` (holding weights ``w``) and
    return the sound and control readings."""
    from bench import check, generator
    eng.reset()
    specs = generator.generate(c.mix, seconds, seed, c.arch["vocab"],
                               c.cell.get("plant"),
                               c.arch["dsa"]["block_k"])
    results = eng.serve(bench_run.requests(specs))
    picked = check.sample(results, c.cell["check"]["sample"], seed)
    prompts = {s.rid: s.prompt for s in specs}
    args = (w, c.arch, c.cell["serving"]["max_len"], c.mix["output"]["max"],
            prompts, picked)
    t0 = time.monotonic()
    sound = check.readings(*args, ref=c.reference)
    t1 = time.monotonic()
    control = check.readings(*args, precision="fp8", ref=c.reference)
    return {"seed": seed, "requests": len(results),
            "ok": sum(r.status == "ok" for r in results),
            "sound": sound, "control": control,
            "sound_s": t1 - t0, "control_s": time.monotonic() - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--root", type=Path, default=bench_run.ROOT,
                    help="the tree whose BENCHMARK.json names the cell "
                         "(default: this checkout)")
    args = ap.parse_args(argv)
    c = bench_run.load_cell(args.workload, args.root)
    import jax
    from bench import weights
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    cfg = bench_run.arch_config(c.arch)
    plant = c.cell.get("plant")
    w = weights.make(c.arch, args.seeds[0], plant)
    eng = bench_run.build_engine(cfg, w, c.cell["serving"])
    bucket = c.reference.geometry(c.arch, c.cell["serving"]["max_len"],
                                  c.mix["prompt"]["max"])["bucket"]
    eng.warmup([bucket])
    bench_run.warm_page_zeroing(eng, c.mix["prompt"]["max"]
                                + c.mix["output"]["max"])
    for i, seed in enumerate(args.seeds):
        if i:
            eng.engine.params = w = None
            gc.collect()
            w = eng.engine.params = weights.make(c.arch, seed, plant)
        print(json.dumps(readings(c, eng, w, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
