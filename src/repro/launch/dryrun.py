import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("DRYRUN_DEVICES", "512")).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
placeholder devices; record memory/cost/collective analysis for §Roofline.

MUST be run as a main module (sets XLA_FLAGS before any jax import):
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all

Cost accounting: XLA's HLO cost analysis counts while-loop bodies ONCE
(trip counts are invisible), so a scanned 80-layer model under-reports
FLOPs ~80x.  The full-graph compile is kept as the *compile proof* and the
*memory analysis* (buffer assignment does account loops); FLOPs/bytes/
collective totals are derived from small UNROLLED lowerings at 1 and 2
layer-groups — the difference isolates the exact per-group cost, which
scales by group count and microbatches:

    train:   mb * (fixed + ng*group [+ n_enc*enc]) + optimizer
    serve:   fixed + ng*group [+ n_enc*enc]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (ARCH_IDS, LONG_CTX_ARCHS, SHAPES, get_config)
from repro.distributed import hlo_analysis as H
from repro.distributed.sharding import (make_rules, resolve_spec, set_rules,
                                        tree_specs)
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import blocks as B
from repro.models.attention import RunFlags
from repro.optim import adamw
from repro.training import steps as ST

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def pick_microbatches(cfg, shape) -> int:
    if shape.kind != "train":
        return 1
    if cfg.d_model >= 6144 or cfg.moe is not None:
        return 8
    if cfg.d_model >= 4096:
        return 4
    return 2


def _extract(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    colls = H.parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": H.collective_summary(colls)}


def _combine(*terms):
    """Linear combination of cost dicts: terms = [(coeff, cost), ...]."""
    out = {"flops": 0.0, "bytes": 0.0,
           "coll": {"total_wire_bytes": 0.0, "ideal_wire_bytes": 0.0,
                    "dci_wire_bytes": 0.0,
                    "n_collectives": 0, "by_op": {}}}
    for coeff, c in terms:
        out["flops"] += coeff * c["flops"]
        out["bytes"] += coeff * c["bytes"]
        out["coll"]["total_wire_bytes"] += coeff * c["coll"]["total_wire_bytes"]
        out["coll"]["ideal_wire_bytes"] += coeff * c["coll"].get(
            "ideal_wire_bytes", c["coll"]["total_wire_bytes"])
        out["coll"]["dci_wire_bytes"] += coeff * c["coll"]["dci_wire_bytes"]
        out["coll"]["n_collectives"] += int(coeff * c["coll"]["n_collectives"])
        for op, d in c["coll"]["by_op"].items():
            t = out["coll"]["by_op"].setdefault(op, {"count": 0,
                                                     "wire_bytes": 0})
            t["count"] += int(coeff * d["count"])
            t["wire_bytes"] += coeff * d["wire_bytes"]
    for k in ("flops", "bytes"):
        out[k] = max(0.0, out[k])
    out["coll"]["total_wire_bytes"] = max(0.0, out["coll"]["total_wire_bytes"])
    out["coll"]["ideal_wire_bytes"] = max(0.0, out["coll"]["ideal_wire_bytes"])
    out["coll"]["dci_wire_bytes"] = max(0.0, out["coll"]["dci_wire_bytes"])
    return out


def _depth_cfg(cfg, n_groups_: int, enc_layers: int):
    period = len(B.group_defs(cfg))
    fk = cfg.moe.first_k_dense if cfg.moe else 0
    kw = dict(n_layers=fk + n_groups_ * period, use_scan=False)
    if cfg.enc_dec:
        kw["n_enc_layers"] = enc_layers
    return dataclasses.replace(cfg, **kw)


def _lower_cost_train(cfg, shape, rules, mesh, flags, gb):
    """REAL train step (optimizer included, donated state) at reduced
    depth, mb=1 — the optimizer-only cost at the same depth is subtracted
    by the caller.  Using the genuine step keeps GSPMD's collective
    schedule honest (a grads-only probe gets its reductions rewritten)."""
    opt = adamw.OptConfig()
    state_st, slog = SP.train_state_structs(cfg, opt)
    sh = dataclasses.replace(shape, global_batch=gb)
    batch_st = SP.batch_structs(cfg, sh, train=True)
    sspecs = tree_specs(state_st, slog, rules, mesh)
    bspecs = tree_specs(batch_st, SP.batch_logical_specs(batch_st), rules,
                        mesh)
    fn = ST.make_train_step(cfg, opt, flags, microbatches=1)
    compiled = jax.jit(fn, in_shardings=(sspecs, bspecs),
                       donate_argnums=(0,)).lower(
        state_st, batch_st).compile()
    return _extract(compiled)


def _lower_cost_opt(cfg, rules, mesh, opt):
    state_st, slog = SP.train_state_structs(cfg, opt)
    sspecs = tree_specs(state_st, slog, rules, mesh)

    def opt_fn(state, grads):
        p2, s2, m = adamw.apply_updates(opt, state["params"], grads,
                                        state["opt"])
        return p2, s2

    gspecs = sspecs["params"]
    compiled = jax.jit(opt_fn, in_shardings=(sspecs, gspecs),
                       donate_argnums=(0,)).lower(
        state_st, state_st["params"]).compile()
    return _extract(compiled)


def _lower_cost_serve(cfg, shape, rules, mesh, flags, kind):
    params_st, plog = SP.model_structs(cfg)
    pspecs = tree_specs(params_st, plog, rules, mesh)
    caches_st, clog = SP.cache_structs(cfg, shape.global_batch,
                                       shape.seq_len, flags)
    cspecs = tree_specs(caches_st, clog, rules, mesh)
    if kind == "prefill":
        batch_st = SP.batch_structs(cfg, shape, train=False)
        bspecs = tree_specs(batch_st, SP.batch_logical_specs(batch_st),
                            rules, mesh)
        fn = ST.make_prefill_step(cfg, flags)
        compiled = jax.jit(fn, in_shardings=(pspecs, bspecs, cspecs),
                           donate_argnums=(2,)).lower(
            params_st, batch_st, caches_st).compile()
    else:
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tspec = resolve_spec((shape.global_batch, 1), ("batch", None),
                             rules, mesh)
        fn = ST.make_decode_fn(cfg, flags)
        compiled = jax.jit(fn, in_shardings=(pspecs, tspec, cspecs),
                           donate_argnums=(2,)).lower(
            params_st, tok, caches_st).compile()
    return _extract(compiled)


def component_costs(cfg, shape, rules, mesh, flags, mb, opt=None):
    """True per-step cost via 1-group/2-group unrolled lowerings."""
    from repro.core.attention import set_probe_unroll
    set_probe_unroll(True)
    try:
        return _component_costs(cfg, shape, rules, mesh, flags, mb, opt)
    finally:
        set_probe_unroll(False)


def _component_costs(cfg, shape, rules, mesh, flags, mb, opt=None):
    kind = shape.kind
    ng = B.n_groups(cfg)
    n_enc = cfg.n_enc_layers if cfg.enc_dec else 0
    with jax.set_mesh(mesh):
        if kind == "train":
            gb = shape.global_batch // mb
            d1, d2 = _depth_cfg(cfg, 1, 1), _depth_cfg(cfg, 2, 1)
            c1 = _lower_cost_train(d1, shape, rules, mesh, flags, gb)
            c2 = _lower_cost_train(d2, shape, rules, mesh, flags, gb)
            o1 = _lower_cost_opt(d1, rules, mesh, adamw.OptConfig())
            o2 = _lower_cost_opt(d2, rules, mesh, adamw.OptConfig())
            # fwd+bwd-only components (optimizer removed):
            c1 = _combine((1.0, c1), (-1.0, o1))
            c2 = _combine((1.0, c2), (-1.0, o2))
            ce = None
            if cfg.enc_dec:
                de = _depth_cfg(cfg, 1, 2)
                ce = _combine(
                    (1.0, _lower_cost_train(de, shape, rules, mesh, flags,
                                            gb)),
                    (-1.0, _lower_cost_opt(de, rules, mesh,
                                           adamw.OptConfig())))
            copt = _lower_cost_opt(cfg, rules, mesh, opt)
        else:
            c1 = _lower_cost_serve(_depth_cfg(cfg, 1, 1), shape, rules,
                                   mesh, flags, kind)
            c2 = _lower_cost_serve(_depth_cfg(cfg, 2, 1), shape, rules,
                                   mesh, flags, kind)
            ce = (_lower_cost_serve(_depth_cfg(cfg, 1, 2), shape, rules,
                                    mesh, flags, kind)
                  if cfg.enc_dec else None)
            copt = None
    group = _combine((1.0, c2), (-1.0, c1))
    terms = [(float(mb), c1), (float(mb) * (ng - 1), group)]
    if ce is not None:
        enc_layer = _combine((1.0, ce), (-1.0, c1))
        terms.append((float(mb) * (n_enc - 1), enc_layer))
    if copt is not None:
        terms.append((1.0, copt))
    return _combine(*terms)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             dsa_mode: str = "auto", fsdp: bool = True, sp: bool = True,
             microbatches: int = 0, fsdp_pod: bool = False, tp: bool = True,
             remat: str = "full", tag: str = "",
             skip_cost: bool = False) -> dict:
    cfg = get_config(arch)
    if remat != "full":
        cfg = dataclasses.replace(cfg, remat_policy=remat)
    shape = SHAPES[shape_name]
    long_ctx = shape_name == "long_500k"
    if dsa_mode == "auto":
        dsa_mode = "block" if cfg.dsa.enabled else "off"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    rules = make_rules(multi_pod=multi_pod, fsdp=fsdp, seq_parallel=sp,
                       long_context=long_ctx, fsdp_pod=fsdp_pod, tp=tp)
    set_rules(rules)
    mb = microbatches or pick_microbatches(cfg, shape)
    opt = adamw.OptConfig(
        moment_dtype="bfloat16" if cfg.num_params() > 5e10 else "float32")
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            flags = RunFlags(mode="train", dsa_mode=dsa_mode)
            state_structs, state_log = SP.train_state_structs(cfg, opt)
            batch_st = SP.batch_structs(cfg, shape, train=True)
            state_specs = tree_specs(state_structs, state_log, rules, mesh)
            batch_specs = tree_specs(
                batch_st, SP.batch_logical_specs(batch_st), rules, mesh)
            fn = ST.make_train_step(cfg, opt, flags, microbatches=mb)
            jfn = jax.jit(fn, in_shardings=(state_specs, batch_specs),
                          donate_argnums=(0,))
            lowered = jfn.lower(state_structs, batch_st)
        elif shape.kind == "prefill":
            flags = RunFlags(mode="prefill", dsa_mode=dsa_mode,
                             with_mse=False)
            params_st, plog = SP.model_structs(cfg)
            batch_st = SP.batch_structs(cfg, shape, train=False)
            caches_st, clog = SP.cache_structs(cfg, shape.global_batch,
                                               shape.seq_len, flags)
            pspecs = tree_specs(params_st, plog, rules, mesh)
            bspecs = tree_specs(batch_st, SP.batch_logical_specs(batch_st),
                                rules, mesh)
            cspecs = tree_specs(caches_st, clog, rules, mesh)
            fn = ST.make_prefill_step(cfg, flags)
            jfn = jax.jit(fn, in_shardings=(pspecs, bspecs, cspecs),
                          donate_argnums=(2,))
            lowered = jfn.lower(params_st, batch_st, caches_st)
        else:  # decode
            flags = RunFlags(mode="decode", dsa_mode="off", with_mse=False,
                             long_context=long_ctx and cfg.dsa.enabled
                             and not cfg.swa_window)
            params_st, plog = SP.model_structs(cfg)
            caches_st, clog = SP.cache_structs(cfg, shape.global_batch,
                                               shape.seq_len, flags)
            tok_st = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            pspecs = tree_specs(params_st, plog, rules, mesh)
            cspecs = tree_specs(caches_st, clog, rules, mesh)
            tspec = resolve_spec((shape.global_batch, 1), ("batch", None),
                                 rules, mesh)
            fn = ST.make_decode_fn(cfg, flags)
            jfn = jax.jit(fn, in_shardings=(pspecs, tspec, cspecs),
                          donate_argnums=(2,))
            lowered = jfn.lower(params_st, tok_st, caches_st)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    raw = _extract(compiled)
    if skip_cost:
        cost = raw
    else:
        cost = component_costs(cfg, shape, rules, mesh, flags, mb, opt)
    n_tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                     else 1)
    n_active = cfg.num_active_params()
    mf = 6.0 * n_active * n_tokens if shape.kind == "train" else (
        2.0 * n_active * n_tokens)
    roof = H.roofline(cost["flops"], cost["bytes"], cost["coll"],
                      model_flops_global=mf, n_chips=n_chips)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "dsa_mode": dsa_mode, "microbatches": mb,
        "fsdp": fsdp, "sp": sp, "fsdp_pod": fsdp_pod, "tp": tp,
        "remat": remat, "tag": tag,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "args_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hbm_bytes": (mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes),
        },
        "cost": {"flops_per_dev": cost["flops"],
                 "bytes_per_dev": cost["bytes"]},
        "collectives": cost["coll"],
        "raw_scanbody_cost": {"flops": raw["flops"], "bytes": raw["bytes"]},
        "roofline": roof,
        "params": cfg.num_params(), "active_params": n_active,
    }
    return rec


def cell_list():
    cells = []
    for arch in ARCH_IDS:
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            cells.append((arch, shape))
        if arch in LONG_CTX_ARCHS:
            cells.append((arch, "long_500k"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dsa", default="auto")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-sp", action="store_true")
    ap.add_argument("--fsdp-pod", action="store_true")
    ap.add_argument("--no-tp", action="store_true",
                    help="pure FSDP/DP rules (no tensor parallelism)")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--skip-cost", action="store_true",
                    help="compile proof + memory only (multi-pod sweep)")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    cells = cell_list() if args.all else [(args.arch, args.shape)]
    failures = 0
    for arch, shape in cells:
        name = f"{arch}__{shape}__{args.mesh}"
        if args.tag:
            name += f"__{args.tag}"
        path = os.path.join(args.out, name + ".json")
        if args.all and os.path.exists(path):
            print(f"[skip] {name}", flush=True)
            continue
        try:
            rec = run_cell(arch, shape, multi_pod=(args.mesh == "multi"),
                           dsa_mode=args.dsa, fsdp=not args.no_fsdp,
                           sp=not args.no_sp, fsdp_pod=args.fsdp_pod,
                           tp=not args.no_tp, remat=args.remat,
                           microbatches=args.microbatches, tag=args.tag,
                           skip_cost=args.skip_cost)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec["roofline"]
            print(f"[ok] {name}: dom={r['dominant']} "
                  f"t={r['bound_step_time_s']:.4f}s "
                  f"hbm={rec['memory']['peak_hbm_bytes']/2**30:.1f}GiB "
                  f"mfu_bound={r.get('mfu_bound', 0):.3f} "
                  f"compile={rec['compile_s']}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(limit=5)
            with open(path + ".fail", "w") as f:
                f.write(traceback.format_exc())
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
