"""Logical-axis sharding rules (MaxText-style) resolved against the mesh.

Weights and activations are annotated with *logical* axis names; a rule table
maps logical axes to mesh axes.  ``resolve_spec`` drops mesh axes that don't
divide the dimension and never uses a mesh axis twice in one spec — this is
what lets one rule table serve 10 architectures (whisper's 12 heads simply
fall back to replicated while qwen's 64 heads shard 16-way).

Parallelism provided (DESIGN.md §3):
  DP   : "batch" -> ("pod", "data")
  FSDP : "embed" (weight d_model axis) -> "data"  (ZeRO-3 weight shard)
  TP   : "heads"/"mlp"/"vocab"/"expert" -> "model"
  SP   : residual-stream "seq_sp" -> "model" between blocks
  EP   : "expert" -> "model" when divisible (deepseek 256, jamba 16),
         falls back to per-expert TP (mixtral 8)
  long-context: "cache_seq" -> "data" (sequence-sharded KV/state cache)
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    batch: Axis = "data"
    seq: Axis = None              # activation seq inside blocks (replicated)
    seq_sp: Axis = "model"        # residual-stream sequence parallelism
    cache_seq: Axis = None        # KV-cache seq; "data" for long-context decode
    embed: Axis = "data"          # FSDP weight shard axis
    embed_act: Axis = None        # activation d_model axis
    mlp: Axis = "model"
    heads: Axis = "model"
    kv_heads: Axis = "model"
    qkv: Axis = None              # head_dim
    vocab: Axis = "model"
    # logits activation vocab axis (embed/embed_act split, same reason):
    # training shards logits over "model" for memory; TP SERVING replicates
    # them (vocab_act=None) and draws inside a replicated shard_map
    # (engine._sample), so every device runs the same full-vocabulary draw
    # as the unsharded program and sampled tokens stay bitwise equal
    vocab_act: Axis = "model"
    expert: Axis = "model"
    lora: Axis = None
    state: Axis = None
    conv: Axis = None
    layers: Axis = None           # scan-stacked leading axis
    pred_k: Axis = None           # DSA projection dim
    blocks: Axis = None           # DSA block indices
    pages: Axis = None            # paged-cache physical page pool rows
    # expert-parallel shard_map dispatch (training only): the serving rules
    # turn it off so a TP serving mesh keeps the SAME capacity-prefill math
    # as unsharded (the EP path has its own dispatch/capacity reduction
    # order — correct, but not bitwise vs the vmap twin)
    moe_ep: bool = True

    def axis(self, name: Optional[str]) -> Axis:
        if name is None:
            return None
        return getattr(self, name)


def make_rules(*, multi_pod: bool = False, fsdp: bool = True,
               seq_parallel: bool = True, long_context: bool = False,
               fsdp_pod: bool = False, tp: bool = True,
               cache_axis: Axis = "auto") -> ShardingRules:
    """Build the rule table for a run.

    fsdp_pod: also shard weights over the pod axis (ZeRO across pods —
    cheaper memory, pays cross-DCI all-gathers; a §Perf experiment).
    cache_axis: KV-cache sequence axis.  "auto" -> "model" (flash-decode
    style seq sharding; GSPMD reduces the softmax across shards), and
    ("data", "model") for long-context (batch=1 cannot use "data").
    """
    if cache_axis == "auto":
        cache_axis = ("data", "model") if long_context else "model"
    if not tp:
        # pure FSDP/DP: batch and weights shard over BOTH axes, no tensor
        # parallelism (right-sizes small models whose TP activation
        # collectives dominate — §Perf)
        both = (("pod", "data", "model") if multi_pod
                else ("data", "model"))
        return ShardingRules(
            batch=both, embed=both if fsdp else None, seq_sp=None,
            mlp=None, heads=None, kv_heads=None, expert=None,
            # vocab stays TP-sharded: embedding/lm_head gradients otherwise
            # all-reduce the full f32 table across all chips (§Perf yi iter 4)
            vocab="model",
            cache_seq=cache_axis,
        )
    batch: Axis = ("pod", "data") if multi_pod else "data"
    embed: Axis = None
    if fsdp:
        embed = ("pod", "data") if (multi_pod and fsdp_pod) else "data"
    return ShardingRules(
        batch=batch,
        embed=embed,
        seq_sp="model" if seq_parallel else None,
        cache_seq=cache_axis,
    )


def make_serving_rules(*, long_context: bool = False,
                       tp: bool = False) -> ShardingRules:
    """Rule table for the resident serving engines (inference.engine /
    inference.scheduler): data parallelism over the batch/slots axis,
    optionally tensor parallelism over "model".

    ``tp=False`` (default): weights stay replicated and every slot's row is
    computed whole on one shard, so per-row math (cache writes, DSA
    selection, softmax, the per-slot PRNG chain) has exactly the unsharded
    reduction order — sharded serving is BITWISE token-exact vs unsharded,
    the multi-device serving contract pinned by tests/test_multidevice.py.
    ``long_context`` additionally lets the KV-cache sequence axis shard
    over "model" (flash-decode style — GSPMD splits the softmax reduction,
    so it is throughput-only, NOT bitwise); a dp-only serving mesh has no
    "model" axis and resolves it to replicated.

    ``tp=True``: weights shard over "model" Megatron-style — Q/K/V/O over
    heads/kv_heads, MLP and MoE expert matrices over mlp/expert,
    embedding/lm_head over vocab — and the resident KV cache, its quant
    scale leaves, and the paged pool rows become head-sharded alongside
    them.  The activation constraints already threaded through the model
    layers make GSPMD insert one all-reduce after each contracting matmul
    (out @ wo over heads, h @ w2 over mlp, the MoE combine over expert);
    per-head attend math is untouched (the embed contraction stays whole),
    so serving stays token-exact vs unsharded at the same seeds/temps.
    The DSA kt/ktb score caches have no head axis — they stay replicated
    over "model", so every shard computes IDENTICAL block top-k indices
    and the gather+attend is local to its own heads (Energon's
    cheap-selection observation).  ``cache_seq`` is forced to None under
    tp: head sharding takes the "model" axis (one-use-per-mesh-axis), and
    seq-sharding the cache would split the softmax (not token-exact)."""
    return ShardingRules(
        batch="data", seq=None, seq_sp=None,
        cache_seq="model" if (long_context and not tp) else None,
        embed=None, embed_act=None,
        mlp="model" if tp else None,
        heads="model" if tp else None,
        kv_heads="model" if tp else None,
        qkv=None,
        # weights shard over vocab; the logits ACTIVATION stays replicated
        # (vocab_act=None) so sampling draws identical random bits — the
        # all-gather after the lm_head matmul concatenates columns whose
        # embed contraction was computed whole per shard
        vocab="model" if tp else None,
        vocab_act=None,
        expert="model" if tp else None,
        # paged resident caches: the physical page pool shards over "data"
        # like the per-slot rows it replaces (non-divisible pool sizes
        # resolve to replicated — graceful); under tp the pool rows are
        # additionally head-sharded via kv_heads above
        pages="data",
        moe_ep=False)


def serving_tp_issues(cfg, tp: int) -> list:
    """Names of the logical weight axes whose model dims do NOT divide a
    ``tp``-way "model" mesh axis (empty list == cfg can TP-shard cleanly).

    Shared by ``launch.mesh.make_serving_mesh`` (up-front ``ValueError``
    naming the offending axis) and ``inference.engine.Engine`` (graceful
    fall-back to replicated weights, mirroring slots-vs-data).  ``cfg`` is
    duck-typed on the ArchConfig fields so this module keeps zero config
    imports.  vocab is deliberately NOT checked: a non-dividing vocab
    simply resolves that one leaf to replicated (per-leaf fallback in
    ``resolve_spec``) without breaking head/mlp sharding."""
    tp = int(tp)
    if tp <= 1:
        return []
    issues = []
    if cfg.n_heads % tp:
        issues.append(f'heads (n_heads={cfg.n_heads} % tp={tp} != 0)')
    n_kv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
    if n_kv % tp:
        issues.append(f'kv_heads (n_kv_heads={n_kv} % tp={tp} != 0)')
    if cfg.d_ff % tp:
        issues.append(f'mlp (d_ff={cfg.d_ff} % tp={tp} != 0)')
    moe = getattr(cfg, "moe", None)
    if moe is not None:
        d_ff_e = getattr(moe, "d_ff_expert", None) or cfg.d_ff
        # expert matrices are (E, d_model, d_ff_expert); either the expert
        # axis or the per-expert ff axis dividing is enough to shard them
        if moe.num_experts % tp and d_ff_e % tp:
            issues.append(
                f'expert (num_experts={moe.num_experts} and '
                f'd_ff_expert={d_ff_e}, neither % tp={tp} == 0)')
    return issues


# Rules used by model code; installed by the launcher before tracing.
_RULES = ShardingRules()


def set_rules(rules: ShardingRules) -> None:
    global _RULES
    _RULES = rules


def get_rules() -> ShardingRules:
    return _RULES


@contextlib.contextmanager
def rules_context(rules: ShardingRules):
    """Temporarily install a rule table (restores the previous one on
    exit) — lets a serving engine trace its dispatches under its own rules
    without clobbering a trainer's global table in the same process."""
    global _RULES
    prev = _RULES
    _RULES = rules
    try:
        yield
    finally:
        _RULES = prev


@contextlib.contextmanager
def compute_context(mesh, rules: Optional[ShardingRules] = None):
    """Install (mesh, rules) around a dispatch so ``shard`` constraints
    resolve during tracing; a plain no-op when ``mesh`` is None (the
    single-device engines keep their exact current programs)."""
    if mesh is None:
        yield
        return
    with contextlib.ExitStack() as stack:
        if rules is not None:
            stack.enter_context(rules_context(rules))
        stack.enter_context(jax.set_mesh(mesh))
        yield


def current_mesh():
    """The mesh installed by ``jax.set_mesh`` (``compute_context``), or
    None when no mesh is active (single-device tests/benches)."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _mesh_axis_sizes(mesh) -> dict:
    return dict(mesh.shape)


def resolve_spec(shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
                 rules: Optional[ShardingRules] = None,
                 mesh=None) -> P:
    """Map logical axes -> PartitionSpec, enforcing divisibility and
    one-use-per-mesh-axis."""
    rules = rules or _RULES
    mesh = mesh or current_mesh()
    if mesh is None or mesh.empty:
        return P(*([None] * len(shape)))
    sizes = _mesh_axis_sizes(mesh)
    used: set = set()
    out = []
    assert len(shape) == len(logical), (shape, logical)
    for dim, name in zip(shape, logical):
        ax = rules.axis(name)
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        picked = []
        prod = 1
        for a in axes:
            if a in used or a not in sizes:
                continue
            if dim % (prod * sizes[a]) == 0:
                picked.append(a)
                prod *= sizes[a]
        for a in picked:
            used.add(a)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    # normalize: P('x', None) and P('x') are the same sharding, but jit's
    # compile cache keys them apart — collapse trailing Nones so every
    # producer of a leaf (device_put, constraints, GSPMD outputs) agrees
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain activation ``x`` to the resolved spec (no-op outside a mesh)."""
    mesh = current_mesh()
    if mesh is None or mesh.empty or not mesh.shape_tuple:
        return x
    spec = resolve_spec(x.shape, tuple(logical), mesh=mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def map_specs(f, spec_tree):
    """Map over a tree whose leaves are logical-axis tuples."""
    return jax.tree.map(f, spec_tree, is_leaf=is_spec_leaf)


def tree_specs(param_tree, logical_tree, rules: Optional[ShardingRules] = None,
               mesh=None):
    """Parallel tree of PartitionSpec from a tree of logical-axis tuples.

    ``param_tree`` may be a tree of arrays or ShapeDtypeStructs.
    """
    def one(p, log):
        return resolve_spec(tuple(p.shape), tuple(log), rules=rules, mesh=mesh)
    return jax.tree.map(one, param_tree, logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


# -- host -> mesh placement (serving engines) --------------------------------


def shard_put(x, *logical, mesh, rules: Optional[ShardingRules] = None):
    """``device_put`` one array with its resolved NamedSharding."""
    import jax.numpy as jnp
    x = jnp.asarray(x)
    spec = resolve_spec(tuple(x.shape), tuple(logical), rules=rules,
                        mesh=mesh)
    return jax.device_put(x, jax.sharding.NamedSharding(mesh, spec))


def shard_put_batch(x, mesh, rules: Optional[ShardingRules] = None):
    """Place an array whose AXIS 0 is the batch/slots axis (decode carries:
    tokens, key chains, masks, temperatures, budgets, draft matrices)."""
    import jax.numpy as jnp
    x = jnp.asarray(x)
    return shard_put(x, *(("batch",) + (None,) * (x.ndim - 1)), mesh=mesh,
                     rules=rules)


def shard_put_tree(tree, logical_tree, mesh,
                   rules: Optional[ShardingRules] = None):
    """``device_put`` a pytree of arrays with its parallel logical-spec
    tree resolved against (mesh, rules) — used to land freshly initialized
    decode caches on the serving mesh before the first dispatch."""
    specs = tree_specs(tree, logical_tree, rules=rules, mesh=mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh, s)),
        tree, specs)


def replicate_put(tree, mesh):
    """Fully replicate a pytree over the mesh (serving weights: every
    shard computes its slot rows whole — the bitwise-exactness choice)."""
    sh = jax.sharding.NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)
