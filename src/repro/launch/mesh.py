"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the "pod"
axis is outer data parallelism crossing the data-center interconnect.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_serving_mesh(dp: int = 0, tp: int = 1, cfg=None):
    """Serving mesh: a single "data" axis over ``dp`` devices (0 = all)
    for data-parallel serving, or a 2-D ``("data", "model")`` mesh when
    ``tp > 1`` — the serving engines shard their slot axis over "data"
    and (tensor parallelism) weights + KV heads over "model"
    (sharding.make_serving_rules).  On CI this is exercised with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the SPMD
    serving program runs without accelerators.

    ``cfg``: optional ArchConfig validated UP FRONT — an indivisible
    head/mlp/expert axis raises a ``ValueError`` naming the offending
    axis here instead of surfacing as a deep XLA sharding error (the
    engines themselves fall back to replicated weights gracefully when
    handed an indivisible mesh without this validation).

    The axes are ``Auto``: GSPMD places the activations from the
    constraints in the model (``jax.make_mesh`` would default to
    ``Explicit`` axes, which ask every gather for an output sharding)."""
    tp = max(1, int(tp))
    if tp == 1:
        return jax.make_mesh((dp or len(jax.devices()),), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    if cfg is not None:
        from repro.distributed.sharding import serving_tp_issues
        issues = serving_tp_issues(cfg, tp)
        if issues:
            raise ValueError(
                f"tp={tp} does not divide arch "
                f"{getattr(cfg, 'name', '?')!r} on axis "
                + "; ".join(issues)
                + " — pick a tp that divides, or serve dp-only "
                "(replicated weights)")
    n = len(jax.devices())
    if n % tp:
        raise ValueError(f"tp={tp} does not divide the {n} visible devices")
    dp = dp or n // tp
    if dp * tp > n:
        raise ValueError(f"dp={dp} x tp={tp} needs {dp * tp} devices, "
                         f"only {n} visible")
    return jax.make_mesh((dp, tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def init_serving_processes(coordinator: str, num_processes: int,
                           process_id: int,
                           local_device_ids=None) -> None:
    """Multi-controller launch (``jax.distributed.initialize``): every
    process runs the SAME serving program and the mesh spans all
    processes' devices, so a dp x tp mesh built afterwards by
    ``make_serving_mesh`` shards weights across hosts — not only forced
    host devices.  Call ONCE per process before any other jax use
    (device enumeration is global after this).

    coordinator: "host:port" of process 0, reachable from every node."""
    if num_processes <= 1:
        return
    kw = dict(coordinator_address=coordinator,
              num_processes=int(num_processes),
              process_id=int(process_id))
    if local_device_ids is not None:
        kw["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kw)
