"""Mesh-sharded serving: BITWISE token-exactness of the data-parallel
engines against their single-device twins.

The serving mesh shards the resident (slots, max_len) cache and every
per-slot carry over the "data" axis with replicated weights
(sharding.make_serving_rules), so each slot's row is computed whole on one
shard — segments, chunked admission, and speculative verify must reproduce
unsharded serving token-for-token at the same seeds/temps/dsa_mode.

CI runs this module in the dedicated multi-device job under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the SPMD serving
program is exercised without accelerators; on a single-device session the
module skips (there is nothing to shard against).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.engine import Engine
from repro.inference.scheduler import ContinuousEngine, Request
from repro.launch.mesh import make_serving_mesh
from repro.models.transformer import init_model

if jax.device_count() < 2:
    pytest.skip(
        "sharded-serving tests need a multi-device mesh — run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8",
        allow_module_level=True)

MAX_LEN = 96
# slots match the forced 8-device data axis, so the slot axis REALLY
# shards (a non-divisible slot count resolves to replicated — graceful,
# but it would exercise nothing here); with fewer forced devices the axis
# still divides 8.
SLOTS = 8


def _mk_requests(vocab, shapes, seed=0, greedy=True):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(1, vocab - 4, size=(l,)).astype(
        np.int32), n, greedy=greedy, seed=rid * 7 + 1)
        for rid, (l, n) in enumerate(shapes)]


@pytest.fixture(scope="module")
def mesh():
    return make_serving_mesh()


@pytest.fixture(scope="module")
def dense(rng):
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    return cfg, params


@pytest.fixture(scope="module")
def dense_pair(dense, mesh):
    cfg, params = dense
    plain = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                             seg_len=4)
    sharded = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                               seg_len=4, mesh=mesh)
    return cfg, params, plain, sharded


def _check_sharded_equals_plain(plain, sharded, mk):
    got_p = plain.run(mk())
    got_s = sharded.run(mk())
    assert set(got_p) == set(got_s)
    for rid in got_p:
        np.testing.assert_array_equal(got_s[rid], got_p[rid],
                                      err_msg=f"rid {rid}")
    return got_p


def test_resident_cache_is_sharded_over_data(dense_pair):
    """The point of the exercise: the resident cache REALLY shards — its
    leaves carry a NamedSharding whose spec names the data axis."""
    _, _, _, sharded = dense_pair
    leaf = jax.tree.leaves(sharded._caches)[0]
    assert "data" in str(leaf.sharding.spec)
    assert len(leaf.sharding.device_set) == jax.device_count()


def test_compiled_cache_builds_land_sharded(dense_pair, mesh):
    """The compiled cache builder lands every leaf with the sharding
    ``shard_put_tree`` gives the eagerly built tree: staging caches at
    both admission widths and the resident cache."""
    from repro.distributed.sharding import shard_put_tree
    from repro.models.transformer import init_cache, unstack_group_caches, \
        unstacked_cache_specs
    cfg, _, _, sharded = dense_pair
    for batch, rows in ((1, 64), (SLOTS, 64), (SLOTS, MAX_LEN)):
        got = (sharded._caches if rows == MAX_LEN
               else sharded._new_cache(batch, rows))
        eager = unstack_group_caches(init_cache(
            cfg, batch, rows, sharded.engine.decode_flags,
            dtype=sharded.engine.cache_dtype))
        want = shard_put_tree(eager, unstacked_cache_specs(cfg, eager),
                              mesh, sharded.engine.shard_rules)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.sharding == w.sharding, (batch, rows, g.shape)
            assert g.shape == w.shape and g.dtype == w.dtype
            assert not np.asarray(g).any()


def test_sharded_run_bitwise_chunked_and_segments(dense_pair):
    """Chunked admission + plain decode segments, mixed lengths and
    n_new=1 retire-at-admission requests: the sharded engine's tokens are
    bitwise the unsharded engine's."""
    cfg, _, plain, sharded = dense_pair
    assert plain.chunked and sharded.chunked
    shapes = [(20, 5), (33, 9), (7, 1), (40, 12), (12, 6), (25, 3),
              (18, 8), (51, 4), (9, 7), (28, 2)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes))
    assert sharded.stats["chunks"] > 0    # chunked admission actually ran


def test_sharded_run_bitwise_sampled_chains(dense_pair):
    """Sampled (greedy=False) per-slot PRNG chains with per-request
    temperatures survive sharding bitwise — the categorical draws happen
    per row on its own shard."""
    cfg, _, plain, sharded = dense_pair

    def mk():
        reqs = _mk_requests(cfg.vocab, [(20, 6), (33, 8), (11, 4), (26, 9)],
                            seed=5, greedy=False)
        for r, t in zip(reqs, (1.0, 0.7, 1.6, 1.0)):
            r.temperature = t
        return reqs

    _check_sharded_equals_plain(plain, sharded, mk)


def test_sharded_run_matches_solo_engine(dense_pair):
    """Transitivity spot-check: sharded continuous serving equals the solo
    single-device Engine.generate per request (same max_len/seed)."""
    cfg, params, _, sharded = dense_pair
    ref = Engine(cfg, params, max_len=MAX_LEN)
    reqs = _mk_requests(cfg.vocab, [(24, 6), (40, 9), (15, 5)], seed=17)
    got = sharded.run(list(reqs))
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp, err_msg=f"rid {r.rid}")


def test_sharded_blocking_admission_bitwise(dense, mesh):
    """LEGACY blocking whole-prompt admission under the mesh (the fallback
    for archs/groups outside the chunk-exactness envelope): batched
    prefill + slot insert stay bitwise."""
    cfg, params = dense
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4,
              chunked_prefill=False)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    assert not sharded.chunked
    shapes = [(20, 5), (33, 9), (12, 6), (25, 3)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=31))


def test_sharded_speculative_segments_bitwise(dense, mesh):
    """Speculative draft-and-verify segments under sharding: the verify
    chunk dispatch, per-slot acceptance, and commit rollbacks reproduce
    the unsharded speculative engine token-for-token."""
    cfg, params = dense
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4, spec=3)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    assert plain.spec and sharded.spec
    shapes = [(20, 8), (33, 12), (12, 6), (40, 10), (18, 5)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=11))
    assert sharded.stats["spec_rounds"] > 0


def test_sharded_dsa_long_context_bitwise(rng, mesh):
    """DSA long-context block decode: predicted-key cache, ktb block sums,
    and per-row block top-k selection shard over slots bitwise."""
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4, long_context=True,
              dsa_mode="block")
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    shapes = [(48, 8), (21, 12), (65, 5), (30, 10), (17, 7)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=21))


def test_sharded_paged_serving_bitwise(rng, mesh):
    """Paged resident cache under the mesh: the physical page pool shards
    over "data" while page tables ride the slot axis — paged sharded
    serving (including a copy-on-write prefix-reuse group) reproduces
    paged unsharded serving token-bitwise, and both drain the pool."""
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4, long_context=True,
              dsa_mode="block", chunk_tokens=16, paged=True)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    rng_np = np.random.default_rng(41)
    sys_p = rng_np.integers(1, cfg.vocab - 4, size=(40,)).astype(np.int32)
    shared_prompts = [np.concatenate([sys_p, rng_np.integers(
        1, cfg.vocab - 4, size=(tail,)).astype(np.int32)])
        for tail in (8, 15, 3)]

    def mk(base=0):
        reqs = _mk_requests(cfg.vocab, [(48, 8), (21, 12), (65, 5),
                                        (30, 10)], seed=43)
        for r in reqs:
            r.rid += base
        reqs += [Request(base + 10 + j, p, 5 + j, seed=j * 7 + 1,
                         prefix_len=40)
                 for j, p in enumerate(shared_prompts)]
        return reqs

    # wave 1 registers the shared prefix (all sharers co-admit: a MISS);
    # wave 2's sharers HIT the registry and skip the shared chunks
    _check_sharded_equals_plain(plain, sharded, mk)
    _check_sharded_equals_plain(plain, sharded, lambda: mk(base=100))
    assert sharded.stats["prefix_tokens_reused"] > 0
    assert (sharded.pool.available()
            == sharded.pool_pages - 1 - 40 // sharded._page_rows)


def test_sharded_engine_generate_bitwise(dense, mesh):
    """Static Engine.generate under the mesh: batched prefill + the fused
    decode scan shard over the batch axis bitwise, greedy and sampled."""
    cfg, params = dense
    plain = Engine(cfg, params, max_len=MAX_LEN)
    sharded = Engine(cfg, params, max_len=MAX_LEN, mesh=mesh)
    rng_np = np.random.default_rng(3)
    prompts = rng_np.integers(1, cfg.vocab - 4, size=(8, 24)).astype(np.int32)
    for greedy in (True, False):
        t_p = plain.generate(prompts, 12, greedy=greedy, seed=5).tokens
        t_s = sharded.generate(prompts, 12, greedy=greedy, seed=5).tokens
        np.testing.assert_array_equal(t_s, t_p, err_msg=f"greedy={greedy}")


def test_sharded_segment_compiles_once(dense, mesh):
    """The recompilation contract survives sharding: varied traffic still
    dispatches exactly ONE decode-segment shape signature (per mesh),
    observed through the telemetry compile watcher — sharded arrays carry
    the same leaf shapes/dtypes, so the watcher needs no mesh handling."""
    from repro.inference.telemetry import Telemetry
    cfg, params = dense
    tel = Telemetry(sample_every=0)
    sharded = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                               seg_len=4, mesh=mesh, telemetry=tel)
    sharded.run(_mk_requests(cfg.vocab, [(5, 3), (37, 6), (60, 9), (14, 2)],
                             seed=5))
    assert tel.compile_count("segment") == 1
    # the compile log survives the engine reset (the programs do too),
    # and fresh same-shape traffic adds no new segment compile
    sharded.reset()
    sharded.run(_mk_requests(cfg.vocab, [(9, 2), (41, 4)], seed=6))
    assert tel.compile_count("segment") == 1


# ---------------------------------------------------------------------------
# Tensor parallelism: 2-D (data, model) meshes shard WEIGHTS over "model"
# ---------------------------------------------------------------------------
# The dp×tp grid keeps the forced 8-device pool honest: 1×2 and 1×4 are
# pure-TP meshes (every slot row's heads/d_ff split across shards), 2×2
# composes TP with the slot sharding above.  Exactness is the contract —
# TP reorders the contracting-matmul reductions (psum over shards) but
# must not flip a single token at the same seeds/temps/dsa_mode.

TP_GRID = [(1, 2), (2, 2), (1, 4)]


def _tp_ids(val):
    return f"dp{val[0]}xtp{val[1]}" if isinstance(val, tuple) else str(val)


@pytest.mark.parametrize("grid", TP_GRID, ids=_tp_ids)
def test_tp_weights_shard_over_model(dense, grid):
    """Weights REALLY shard: engine.tp records the model-axis width, the
    attention projections carry a NamedSharding naming "model", and the
    per-device resident weight bytes shrink ~1/tp (norm/bias leaves stay
    replicated, so the ratio is a touch above the ideal)."""
    dp, tp = grid
    cfg, params = dense
    mesh = make_serving_mesh(dp=dp, tp=tp, cfg=cfg)
    eng = Engine(cfg, params, max_len=MAX_LEN, mesh=mesh)
    assert eng.tp == tp
    specs = [str(leaf.sharding.spec)
             for leaf in jax.tree.leaves(eng.params)]
    assert any("model" in s for s in specs)
    full = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    ratio = eng.weight_bytes_per_device() / full
    assert ratio <= 1.0 / tp + 0.08, ratio


@pytest.mark.parametrize("grid", TP_GRID, ids=_tp_ids)
def test_tp_continuous_chunked_bitwise(dense, grid):
    """Chunked admission + decode segments under dp×tp: one SPMD program,
    tokens bitwise the unsharded engine's."""
    dp, tp = grid
    cfg, params = dense
    mesh = make_serving_mesh(dp=dp, tp=tp, cfg=cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    assert sharded.engine.tp == tp
    shapes = [(20, 5), (33, 9), (7, 1), (40, 12), (12, 6), (25, 3)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=19))
    assert sharded.stats["chunks"] > 0


@pytest.mark.parametrize("dsa_mode", ["block", "kernel"])
def test_tp_dsa_modes_bitwise(dense, dsa_mode):
    """DSA under TP: kt/ktb score caches have no head axis, so they stay
    replicated over "model" — every shard computes the SAME block top-k
    and gathers its own heads' KV locally.  Token-bitwise at dp=2,tp=2."""
    cfg, params = dense
    mesh = make_serving_mesh(dp=2, tp=2, cfg=cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4, dsa_mode=dsa_mode)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    shapes = [(20, 6), (33, 9), (14, 4), (27, 8)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=23))


def test_tp_sampled_chains_bitwise(dense):
    """Sampled per-slot PRNG chains with mixed temperatures under TP: the
    categorical draws replicate over "model" (vocab_act=None pins the
    logits; the draw itself runs in a replicated shard_map), so the
    threefry stream is bit-identical to unsharded."""
    cfg, params = dense
    mesh = make_serving_mesh(dp=1, tp=2, cfg=cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)

    def mk():
        reqs = _mk_requests(cfg.vocab, [(20, 6), (33, 8), (11, 4), (26, 9)],
                            seed=29, greedy=False)
        for r, t in zip(reqs, (1.0, 0.7, 1.6, 1.0)):
            r.temperature = t
        return reqs

    _check_sharded_equals_plain(plain, sharded, mk)


def test_tp_blocking_admission_bitwise(dense):
    """Legacy blocking whole-prompt admission under TP stays bitwise."""
    cfg, params = dense
    mesh = make_serving_mesh(dp=2, tp=2, cfg=cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4,
              chunked_prefill=False)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    assert not sharded.chunked
    shapes = [(20, 5), (33, 9), (12, 6), (25, 3)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=37))


def test_tp_paged_bitwise(dense):
    """Paged resident cache under TP: pool rows shard their head axis over
    "model" while page tables stay per-"data" — paged TP serving equals
    paged unsharded serving token-bitwise."""
    cfg, params = dense
    mesh = make_serving_mesh(dp=2, tp=2, cfg=cfg)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4, paged=True)
    plain = ContinuousEngine(cfg, params, **kw)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    shapes = [(20, 6), (33, 9), (14, 4), (27, 8)]
    _check_sharded_equals_plain(plain, sharded,
                                lambda: _mk_requests(cfg.vocab, shapes,
                                                     seed=41))


@pytest.mark.parametrize("grid", TP_GRID, ids=_tp_ids)
def test_tp_static_generate_bitwise(dense, grid):
    """Static Engine.generate under dp×tp: batched prefill + fused decode
    scan with model-sharded weights, greedy AND sampled bitwise."""
    dp, tp = grid
    cfg, params = dense
    mesh = make_serving_mesh(dp=dp, tp=tp, cfg=cfg)
    plain = Engine(cfg, params, max_len=MAX_LEN)
    sharded = Engine(cfg, params, max_len=MAX_LEN, mesh=mesh)
    rng_np = np.random.default_rng(3)
    prompts = rng_np.integers(1, cfg.vocab - 4, size=(8, 24)).astype(np.int32)
    for greedy in (True, False):
        t_p = plain.generate(prompts, 12, greedy=greedy, seed=5).tokens
        t_s = sharded.generate(prompts, 12, greedy=greedy, seed=5).tokens
        np.testing.assert_array_equal(t_s, t_p, err_msg=f"greedy={greedy}")


def test_tp_segment_compiles_once(dense):
    """The recompilation contract holds per (mesh, rules): varied traffic
    on a dp=2,tp=2 mesh still dispatches exactly ONE decode-segment shape
    signature."""
    from repro.inference.telemetry import Telemetry
    cfg, params = dense
    mesh = make_serving_mesh(dp=2, tp=2, cfg=cfg)
    tel = Telemetry(sample_every=0)
    sharded = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                               seg_len=4, mesh=mesh, telemetry=tel)
    sharded.run(_mk_requests(cfg.vocab, [(5, 3), (37, 6), (60, 9), (14, 2)],
                             seed=5))
    assert tel.compile_count("segment") == 1
    sharded.reset()
    sharded.run(_mk_requests(cfg.vocab, [(9, 2), (41, 4)], seed=6))
    assert tel.compile_count("segment") == 1


def test_tp_mesh_divisibility_error(rng):
    """make_serving_mesh(cfg=...) rejects an indivisible tp up front with
    a ValueError NAMING the offending axis."""
    cfg = reduced(get_config("yi_6b"))       # n_kv_heads=2: tp=4 indivisible
    with pytest.raises(ValueError, match="kv_heads"):
        make_serving_mesh(dp=2, tp=4, cfg=cfg)


def test_tp_indivisible_falls_back_replicated(rng):
    """An Engine handed a 2-D mesh whose "model" width does not divide the
    arch falls back to replicated weights GRACEFULLY (tp=1, full weight
    bytes per device) and stays token-exact."""
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    mesh = make_serving_mesh(tp=4)           # no cfg: validation deferred
    sharded = Engine(cfg, params, max_len=MAX_LEN, mesh=mesh)
    assert sharded.tp == 1
    full = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    assert sharded.weight_bytes_per_device() == full
    plain = Engine(cfg, params, max_len=MAX_LEN)
    rng_np = np.random.default_rng(3)
    prompts = rng_np.integers(1, cfg.vocab - 4, size=(8, 24)).astype(np.int32)
    for greedy in (True, False):
        t_p = plain.generate(prompts, 10, greedy=greedy, seed=5).tokens
        t_s = sharded.generate(prompts, 10, greedy=greedy, seed=5).tokens
        np.testing.assert_array_equal(t_s, t_p, err_msg=f"greedy={greedy}")


def test_tp_decode_segment_collective_budget(dense):
    """The lowered pure-TP decode segment carries EXACTLY the Megatron
    collective budget — one all-reduce per layer per contracting matmul
    group (attention out-proj, MLP down-proj) plus the embedding-gather
    all-reduce and one weight-shaped lm-head all-gather — and the counts
    do not grow with seg_len (no collective is added per token)."""
    from repro.distributed.hlo_analysis import (
        assert_collectives_token_invariant, check_tp_decode_collectives)
    cfg, params = dense
    mesh = make_serving_mesh(dp=1, tp=2, cfg=cfg)

    def seg_text(seg_len):
        eng = ContinuousEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                               seg_len=seg_len, mesh=mesh)
        remaining = np.zeros(SLOTS, np.int32)
        poison = np.zeros(SLOTS, bool)
        with eng._ctx():
            return eng._segment.lower(
                eng.engine.params, eng._put_b(eng._tok), eng._caches,
                eng._put_b(eng._keys), eng._put_b(eng._active),
                eng._put_b(eng._greedy), eng._put_b(eng._temps),
                eng._put_b(remaining), eng._put_b(poison),
                flags=eng._flags("decode")).compile().as_text()

    t4, t8 = seg_text(4), seg_text(8)
    counts = check_tp_decode_collectives(t4, cfg.n_layers)
    assert counts["all-reduce"] == 2 * cfg.n_layers + 1
    assert_collectives_token_invariant(t4, t8)
