"""Continuous-batching scheduler: per-request token-exactness vs the
static engine (greedy AND sampled key chains) through the DEFAULT chunked
admission path, chunked-vs-blocking admission equivalence (including chunk
sizes that don't divide the prompt length), slot-reuse isolation (no
KV/ktb leakage across tenants), DSA long-context serving (block AND fused
chunk kernel), per-request temperature / dsa_mode overrides, and the
TTFT anchoring on the chunked/prefix-hit admission path (the
fixed-compile-set contract moved to tests/test_telemetry.py)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.engine import Engine
from repro.inference.scheduler import (ContinuousEngine, Request,
                                       RequestResult, summarize)
from repro.models.transformer import init_model

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # CI installs hypothesis; local minimal envs skip
    HAVE_HYPOTHESIS = False

MAX_LEN = 96


@pytest.fixture(scope="module")
def dense(rng):
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4)
    ref = Engine(cfg, params, max_len=MAX_LEN)
    return cfg, params, ce, ref


@pytest.fixture(scope="module")
def dsa(rng):
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    kw = dict(long_context=True, dsa_mode="block")
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          **kw)
    ref = Engine(cfg, params, max_len=MAX_LEN, **kw)
    return cfg, params, ce, ref


def _mk_requests(vocab, shapes, seed=0, greedy=True):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(1, vocab - 4, size=(l,)).astype(
        np.int32), n, greedy=greedy, seed=rid * 7 + 1)
        for rid, (l, n) in enumerate(shapes)]


def _check_exact(ce, ref, reqs):
    got = ce.run(reqs)
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp,
                                      err_msg=f"rid {r.rid}")
    return got


def test_scheduler_token_exact_dense(dense):
    """Any admission order / mixed lengths: every request gets EXACTLY its
    solo static-batch tokens (same max_len), including n_new=1 requests
    that retire at admission."""
    cfg, _, ce, ref = dense
    reqs = _mk_requests(cfg.vocab, [(20, 5), (33, 9), (7, 1), (40, 12),
                                    (12, 6), (25, 3), (18, 8)])
    _check_exact(ce, ref, reqs)


def test_scheduler_token_exact_dsa(dsa):
    """DSA long-context serving: block selection sees the same cache
    geometry per slot, so tokens stay exact through the predicted-key
    cache, ktb block sums, and slot-ragged kv_len."""
    cfg, _, ce, ref = dsa
    reqs = _mk_requests(cfg.vocab, [(48, 8), (21, 12), (65, 5), (30, 10),
                                    (17, 7)])
    _check_exact(ce, ref, reqs)


def test_scheduler_sampled_chain_matches_engine(dense):
    """greedy=False: the per-slot PRNG chain (split + categorical per row)
    replays Engine's B=1 chain bit-for-bit at the request's seed."""
    cfg, _, ce, ref = dense
    reqs = _mk_requests(cfg.vocab, [(20, 6), (33, 8), (11, 4)],
                        greedy=False)
    _check_exact(ce, ref, reqs)


def test_slot_reuse_never_leaks(dense):
    """A request's tokens are independent of what previously occupied its
    slot: served alone vs served after heavy slot-churning traffic."""
    cfg, _, ce, ref = dense
    probe = _mk_requests(cfg.vocab, [(26, 7)], seed=3)[0]
    alone = ce.run([probe])[probe.rid]
    churn = _mk_requests(cfg.vocab, [(40, 9), (15, 4), (31, 6), (22, 11),
                                     (9, 2)], seed=4)
    late = Request(99, probe.prompt, probe.n_new, greedy=probe.greedy,
                   seed=probe.seed)
    mixed = ce.run(churn + [late])
    np.testing.assert_array_equal(alone, mixed[99])


def test_chunked_is_default_and_stats_count_chunks(dense):
    """Chunked admission is the default for bucketable non-MoE archs and
    actually runs (chunk stats advance; no blocking prefill seconds)."""
    cfg, _, ce, ref = dense
    assert ce.chunked
    ce.reset()
    ce.run(_mk_requests(cfg.vocab, [(40, 6), (22, 4)], seed=9))
    assert ce.stats["chunks"] > 0
    assert ce.stats["prefill_s"] == 0.0   # legacy blocking path never ran


def test_chunked_matches_blocking_and_engine_nondivisible_chunks(dense):
    """Chunk width 16 over prompts 20/33/65 (chunks never divide the
    prompt): chunked admission reproduces BOTH the blocking-admission
    scheduler and solo Engine.generate token-bitwise, greedy and
    sampled."""
    cfg, params, _, ref = dense
    shapes = [(20, 5), (33, 7), (65, 6), (16, 4)]
    reqs = _mk_requests(cfg.vocab, shapes, seed=21)
    reqs += _mk_requests(cfg.vocab, [(33, 6), (20, 4)], seed=22,
                         greedy=False)
    for r in reqs[4:]:
        r.rid += 10
    chunked = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                               seg_len=4, chunk_tokens=16)
    blocking = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                                seg_len=4, chunked_prefill=False)
    assert chunked.chunked and not blocking.chunked
    got_c = chunked.run(list(reqs))
    got_b = blocking.run(list(reqs))
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed).tokens[0]
        np.testing.assert_array_equal(got_c[r.rid], exp,
                                      err_msg=f"chunked rid {r.rid}")
        np.testing.assert_array_equal(got_b[r.rid], exp,
                                      err_msg=f"blocking rid {r.rid}")


def test_chunked_dsa_block_and_kernel_exact(dsa):
    """DSA chunked admission: the incremental kt/ktb extension and the
    chunked sparse selection reproduce whole-prompt prefill through BOTH
    the XLA block path and the fused Pallas chunk kernel."""
    cfg, params, ce, ref = dsa
    assert ce.chunked
    shapes = [(48, 6), (21, 8), (65, 5), (30, 4)]
    for chunk_tokens in (16, 32):
        cek = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                               seg_len=4, long_context=True,
                               dsa_mode="kernel", chunk_tokens=chunk_tokens)
        refk = Engine(cfg, params, max_len=MAX_LEN, long_context=True,
                      dsa_mode="kernel")
        reqs = _mk_requests(cfg.vocab, shapes, seed=31)
        got = cek.run(reqs)
        for r in reqs:
            exp = refk.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                                seed=r.seed).tokens[0]
            np.testing.assert_array_equal(
                got[r.rid], exp,
                err_msg=f"kernel chunk={chunk_tokens} rid {r.rid}")


def test_chunked_bucket_smaller_than_dsa_block(rng):
    """Regression: prompt buckets SMALLER than dsa.block_k (the common
    case at production 128x128 blocks) must still chunk-admit — the chunk
    width floors at the block size and the overhang past the bucket drops
    out of bounds, keeping the bucket's selection geometry."""
    import dataclasses as dc
    cfg = reduced(get_config("yi_6b"))
    cfg = dc.replace(cfg, dsa=dc.replace(cfg.dsa, block_q=32, block_k=32))
    params, _ = init_model(rng, cfg)
    kw = dict(long_context=True, dsa_mode="block")
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          chunk_tokens=16, **kw)
    assert ce.chunked and ce.chunk_tokens == 32
    ref = Engine(cfg, params, max_len=MAX_LEN, **kw)
    reqs = _mk_requests(cfg.vocab, [(10, 4), (20, 5), (40, 6)], seed=71)
    _check_exact(ce, ref, reqs)


def test_per_request_temperature(dense):
    """Request.temperature scales that request's sampled chain exactly as
    Engine.generate(temperature=...) — and temperature 1.0 stays
    bit-identical to the unscaled chain."""
    cfg, _, ce, ref = dense
    ce.reset()
    rng = np.random.default_rng(41)
    reqs = [Request(rid, rng.integers(1, cfg.vocab - 4, size=(l,)).astype(
        np.int32), n, greedy=False, seed=rid * 3 + 1, temperature=t)
        for rid, (l, n, t) in enumerate([(20, 6, 0.7), (33, 5, 1.0),
                                         (14, 7, 1.6)])]
    got = ce.run(reqs)
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed, temperature=r.temperature).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp,
                                      err_msg=f"rid {r.rid} T={r.temperature}")


def test_per_request_dsa_mode_override(dsa):
    """Request.dsa_mode overrides the engine's decode path per request
    (mode-affine segments — the engine drains, switches mode, and each
    request matches Engine.generate at ITS mode)."""
    cfg, _, ce, ref = dsa
    ce.reset()
    rng = np.random.default_rng(51)
    modes = ["block", "kernel", "faithful", None, "off"]
    reqs = [Request(rid, rng.integers(1, cfg.vocab - 4,
                                      size=(17 + 7 * rid,)).astype(np.int32),
                    4 + rid, seed=rid, dsa_mode=m)
            for rid, m in enumerate(modes)]
    got = ce.run(list(reqs))
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed, dsa_mode=r.dsa_mode).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp,
                                      err_msg=f"rid {r.rid} mode={r.dsa_mode}")


def test_mla_dsa_override_falls_back_to_blocking(rng):
    """A per-request dsa_mode override that leaves the chunk-exactness
    envelope (DSA-over-MLA has no predicted-key cache to resume) must fall
    back to blocking admission for that group — and stay token-exact vs
    Engine.generate at the same override."""
    import dataclasses as dc
    cfg = reduced(get_config("deepseek_v3"))
    cfg = dc.replace(cfg, moe=None, n_layers=2)      # pure MLA, DSA enabled
    assert cfg.dsa.enabled
    params, _ = init_model(rng, cfg)
    kw = dict(long_context=True, dsa_mode="off")
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          **kw)
    assert ce.chunked                  # chunkable at the engine-level mode
    ref = Engine(cfg, params, max_len=MAX_LEN, **kw)
    rng_np = np.random.default_rng(81)
    reqs = [Request(rid, rng_np.integers(1, cfg.vocab - 4,
                                         size=(20 + 9 * rid,)).astype(
                        np.int32), 4 + rid, seed=rid, dsa_mode=m)
            for rid, m in enumerate([None, "block", "faithful"])]
    got = ce.run(list(reqs))
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed, dsa_mode=r.dsa_mode).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp,
                                      err_msg=f"rid {r.rid} mode={r.dsa_mode}")


def test_dsa_mode_override_rejected_without_cache(dense):
    """A dense (non-long-context) engine holds no predicted-key cache: DSA
    mode overrides must be rejected at submit, not crash a segment."""
    cfg, _, ce, ref = dense
    with pytest.raises(ValueError):
        ce.submit(Request(123, np.ones((8,), np.int32), 2,
                          dsa_mode="block"))
    with pytest.raises(ValueError):
        ce.submit(Request(124, np.ones((8,), np.int32), 2, temperature=0.0))


def test_ttft_reported_before_finish(dense):
    """RequestResult carries a first-token timestamp: TTFT <= latency and
    the chunked path stamps it when the last chunk completes."""
    cfg, params, _, ref = dense
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          chunk_tokens=16)
    reqs = _mk_requests(cfg.vocab, [(40, 12), (20, 8)], seed=61)
    for r in reqs:
        ce.submit(r)
    results = []
    import itertools
    counter = itertools.count()
    clock = lambda: float(next(counter))       # monotone fake clock
    while ce.has_work():
        ce.admit_ready(clock, results)
        ce.step_prefill(clock, results)
        if any(s is not None for s in ce._slot):
            ce.run_segment(clock, results)
    assert len(results) == 2
    for r in results:
        assert r.first_token_s <= r.finish_s
        assert r.ttft_s <= r.latency_s


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["chunked", "blocking"])
def test_admission_stamps_order(dense, chunked):
    """``admit_s`` is when the request's admission group started, on both
    admission paths: arrival <= admit <= first token <= finish for every
    request; one queued behind a full slot set was admitted only after a
    slot freed (so after its arrival); and a multi-chunk prompt's
    admission starts before its first token."""
    cfg, params, _, _ = dense
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          chunk_tokens=16, chunked_prefill=chunked)
    shapes = [(40, 6), (36, 5), (40, 4), (20, 1), (33, 6)]
    results = ce.serve(_mk_requests(cfg.vocab, shapes, seed=5))
    assert [r.status for r in results] == ["ok"] * len(shapes)
    for r in results:
        assert r.arrival_s <= r.admit_s <= r.first_token_s <= r.finish_s
        assert r.admit_s < r.first_token_s          # >= 2 chunks each
    first_free = min(r.finish_s for r in results[:2])
    for r in results[2:]:                 # queued behind both slots
        assert r.admit_s > r.arrival_s
        assert r.admit_s >= first_free


def test_moe_dense_prefill_enables_chunked_admission(rng):
    """moe_prefill="dense": whole-prompt prefill routes the decode-dense
    expert path, so MoE archs chunk-admit (can_chunk_prefill flips) and
    stay bitwise token-exact vs Engine.generate at the same option."""
    cfg = reduced(get_config("deepseek_v3"))        # MLA + MoE arch
    assert cfg.moe is not None
    params, _ = init_model(rng, cfg)
    default = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                               seg_len=4)
    assert not default.chunked                      # capacity-path prefill
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          moe_prefill="dense", chunk_tokens=16)
    assert ce.chunked
    ref = Engine(cfg, params, max_len=MAX_LEN, moe_prefill="dense")
    reqs = _mk_requests(cfg.vocab, [(20, 5), (33, 7), (17, 4)], seed=91)
    reqs += _mk_requests(cfg.vocab, [(20, 4)], seed=92, greedy=False)
    reqs[-1].rid += 10
    got = ce.run(list(reqs))
    assert ce.stats["chunks"] > 0 and ce.stats["prefill_s"] == 0.0
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp, err_msg=f"rid {r.rid}")


def _drain_with_admit_order(ce, reqs):
    import itertools
    counter = itertools.count()
    clock = lambda: float(next(counter))
    for r in reqs:
        ce.submit(r)
    results = []
    while ce.has_work():
        ce.admit_ready(clock, results)
        ce.step_prefill(clock, results)
        if any(s is not None for s in ce._slot):
            ce.run_segment(clock, results)
    return {r.rid: r for r in results}


def test_mode_wait_aging_unstarves_other_mode_requests(dsa):
    """Mode-affine starvation fix: a queued other-mode request older than
    ``max_mode_wait_s`` forces a drain/mode-switch instead of waiting for
    sustained default-mode traffic to stop.  With the budget at 0 the
    other-mode request is admitted before later default-mode traffic;
    without aging it is admitted last."""
    cfg, params, _, ref = dsa
    shapes = [(20, 12, None), (20, 4, "off"), (20, 4, None), (20, 4, None)]
    rng_np = np.random.default_rng(71)
    prompts = [rng_np.integers(1, cfg.vocab - 4, size=(l,)).astype(np.int32)
               for l, _, _ in shapes]
    mk = lambda: [Request(rid, prompts[rid], n, seed=rid, dsa_mode=m)
                  for rid, (_, n, m) in enumerate(shapes)]
    aged = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                            seg_len=4, long_context=True, dsa_mode="block",
                            max_mode_wait_s=0.0)
    res_aged = _drain_with_admit_order(aged, mk())
    assert res_aged[1].admit_s < res_aged[3].admit_s
    plain = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                             seg_len=4, long_context=True, dsa_mode="block")
    res_plain = _drain_with_admit_order(plain, mk())
    assert res_plain[1].admit_s > res_plain[3].admit_s   # the starvation
    # aging only reorders admission — tokens stay exact per request
    for rid, r in res_aged.items():
        exp = ref.generate(prompts[rid][None], r.n_new,
                           seed=rid, dsa_mode=shapes[rid][2]).tokens[0]
        np.testing.assert_array_equal(r.tokens, exp, err_msg=f"rid {rid}")


def test_summarize_empty_results_returns_zeroed_metrics():
    """Regression: an aborted serve / smoke bench with no completed
    requests must summarize to zeroed metrics, not traceback on the
    percentile of an empty array."""
    s = summarize([], 1.25)
    assert s["n_requests"] == 0 and s["delivered_tokens"] == 0
    assert s["wall_s"] == 1.25 and s["goodput_tok_s"] == 0.0
    for k in ("p50_latency_s", "p95_latency_s", "mean_latency_s",
              "p50_ttft_s", "p95_ttft_s"):
        assert s[k] == 0.0
    # non-empty keeps the same key set (nothing downstream re-keys)
    full = summarize([RequestResult(0, np.zeros((3,), np.int32), 4, 3,
                                    0.0, 0.1, 0.5, first_token_s=0.2)], 1.0)
    assert set(full) == set(s)


# NOTE the fixed-compile-set contract (segment/chunk/insert/verify compile
# counts across dense/paged/quant/spec engines) lives in
# tests/test_telemetry.py::test_recompilation_contract, asserted through
# the telemetry compile watcher instead of jit cache-size introspection.


# -- paged KV cache + copy-on-write prefix reuse -----------------------------


@pytest.fixture(scope="module")
def dense_paged(dense):
    cfg, params, _, ref = dense
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          paged=True)
    return cfg, params, ce, ref


def test_paged_token_exact_dense(dense_paged):
    """Paged resident cache (block-table indirection over the shared page
    pool): greedy AND sampled serving stays BITWISE token-exact vs the
    dense solo engine, and retire/readmit churn returns every page."""
    cfg, _, ce, ref = dense_paged
    assert ce.paged and ce.pool is not None
    reqs = _mk_requests(cfg.vocab, [(20, 5), (33, 9), (7, 1), (40, 12),
                                    (12, 6)])
    extra = _mk_requests(cfg.vocab, [(33, 6), (20, 4)], seed=2,
                         greedy=False)
    for r in extra:
        r.rid += 10
    _check_exact(ce, ref, reqs + extra)
    assert ce.pool.available() == ce.pool_pages - 1   # nothing leaked


def test_paged_token_exact_dsa_block_and_kernel(dsa):
    """DSA long-context paged serving: logical block selection translates
    through the page table (XLA block path AND the fused Pallas paged
    gather kernel) token-bitwise vs the dense engine."""
    cfg, params, _, _ = dsa
    shapes = [(48, 6), (21, 8), (65, 5), (30, 4)]
    for mode in ("block", "kernel"):
        ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                              seg_len=4, long_context=True, dsa_mode=mode,
                              paged=True)
        ref = Engine(cfg, params, max_len=MAX_LEN, long_context=True,
                     dsa_mode=mode)
        reqs = _mk_requests(cfg.vocab, shapes, seed=131)
        _check_exact(ce, ref, reqs)
        assert ce.pool.available() == ce.pool_pages - 1, mode


def test_paged_prefix_reuse_exact_and_skips_chunks(dsa):
    """Copy-on-write prefix sharing: requests declaring a common prefix
    map the same physical pages, skip the shared whole-page chunks at
    admission (prefix registry HIT), and still emit BITWISE the dense
    engine's tokens; the registry keeps the shared pages alive after the
    readers retire."""
    cfg, params, _, _ = dsa
    rng = np.random.default_rng(141)
    sys_p = rng.integers(1, cfg.vocab - 4, size=(40,)).astype(np.int32)

    def mk(rid, tail, n, greedy=True):
        p = np.concatenate([sys_p, rng.integers(
            1, cfg.vocab - 4, size=(tail,)).astype(np.int32)])
        return Request(rid, p, n, greedy=greedy, seed=rid * 7 + 1,
                       prefix_len=40)

    reqs = [mk(0, 8, 6), mk(1, 15, 5), mk(2, 3, 7, greedy=False),
            mk(3, 20, 4), mk(4, 11, 5), mk(5, 6, 6, greedy=False)]
    kw = dict(slots=2, max_len=MAX_LEN, seg_len=4, long_context=True,
              dsa_mode="block", chunk_tokens=16)
    ce = ContinuousEngine(cfg, params, paged=True, **kw)
    plain = ContinuousEngine(cfg, params, **kw)
    ref = Engine(cfg, params, max_len=MAX_LEN, long_context=True,
                 dsa_mode="block")
    _check_exact(ce, ref, reqs)
    assert ce.stats["prefix_hits"] > 0
    assert ce.stats["prefix_tokens_reused"] > 0
    plain.run(list(reqs))
    assert ce.stats["chunks"] < plain.stats["chunks"]   # chunks skipped
    # the LRU registry still owns the shared pages; everything else is back
    n_sh = 40 // ce._page_rows
    assert len(ce.pool.prefixes) == 1
    assert ce.pool.available() == ce.pool_pages - 1 - n_sh


def test_prefix_hit_ttft_anchors_at_finishing_chunk(dense):
    """TTFT anchoring audit pin: on the chunked path ``first_token_s`` is
    sampled AFTER the finishing chunk's host sync — so a prefix HIT
    (pool-seeded staging, shared chunks skipped) anchors after only the
    chunks that actually ran.  A fake clock that counts ``_chunk``
    dispatches makes the anchor deterministic: 2-chunk prompts report
    first_token_s == 2.0 undeclared (and on the registering MISS) but
    == 1.0 on the HIT — and tokens stay bitwise equal across waves."""
    cfg, params, _, _ = dense
    eng = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                           seg_len=4, paged=True)
    rng = np.random.default_rng(0)
    pfx = rng.integers(1, cfg.vocab - 4, size=(64,)).astype(np.int32)
    tails = [rng.integers(1, cfg.vocab - 4, size=(n,)).astype(np.int32)
             for n in (4, 7)]                  # prompts 68/71: 2 chunks

    def wave(base, declare):
        return [Request(base + j, np.concatenate([pfx, t]), 6, greedy=True,
                        seed=j * 3 + 1, prefix_len=64 if declare else 0)
                for j, t in enumerate(tails)]

    calls = {"n": 0}
    orig = eng._chunk
    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)
    clock = lambda: float(calls["n"])

    def drive(reqs):
        calls["n"] = 0
        for r in reqs:
            eng.submit(r)
        results = []
        while eng.has_work():
            eng.admit_ready(clock, results)
            eng.step_prefill(clock, results)
            if any(s is not None for s in eng._slot):
                eng._step_decode(clock, results)
        results.extend(eng._pending)
        eng._pending.clear()
        return {r.rid - reqs[0].rid: r for r in results}

    eng._chunk = counting
    try:
        plain = drive(wave(0, False))          # both chunks run
        miss = drive(wave(100, True))          # registers; still 2 chunks
        hit = drive(wave(200, True))           # seeded: finishing only
    finally:
        eng._chunk = orig
    for j in range(len(tails)):
        assert plain[j].first_token_s == 2.0
        assert miss[j].first_token_s == 2.0
        assert hit[j].first_token_s == 1.0     # skip capped at chunks-1
        np.testing.assert_array_equal(plain[j].tokens, hit[j].tokens)
        np.testing.assert_array_equal(plain[j].tokens, miss[j].tokens)
        assert hit[j].ttft_s == 1.0            # arrival_s == 0


def test_paged_small_pool_backpressure_exact(dense):
    """A pool smaller than slots*max_len: admission caps groups at what
    the pool can fund and later requests wait for retirements — tokens
    stay exact and the drained pool is whole again."""
    cfg, params, _, ref = dense
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          paged=True, pool_pages=5)      # 4 usable pages
    reqs = _mk_requests(cfg.vocab, [(20, 25), (17, 30), (30, 3), (20, 5)],
                        seed=151)
    _check_exact(ce, ref, reqs)
    assert ce.pool.available() == 4


def test_paged_admission_validation(dense_paged):
    """Up-front refusals: a request whose pages can NEVER fit the pool, a
    prefix_len outside the prompt, and a max_len that isn't whole pages
    all fail at submit/construction with clear ValueErrors."""
    cfg, params, ce, _ = dense_paged
    small = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                             seg_len=4, paged=True, pool_pages=4)
    with pytest.raises(ValueError, match="pages"):
        small.submit(Request(1, np.ones((60,), np.int32), 20))
    with pytest.raises(ValueError, match="prefix_len"):
        ce.submit(Request(2, np.ones((8,), np.int32), 2, prefix_len=9))
    with pytest.raises(ValueError, match="page size"):
        ContinuousEngine(cfg, params, slots=2, max_len=90, seg_len=4,
                         paged=True)


def test_engine_generate_rejects_overflow(dense):
    """Admission-time validation regression: Engine.generate refuses
    prompt_len + n_new > max_len up front (clear ValueError, no cache
    overflow), and per-row ``lengths`` count — a padded matrix whose TRUE
    lengths fit is accepted."""
    cfg, _, _, ref = dense
    with pytest.raises(ValueError, match="max_len"):
        ref.generate(np.ones((1, 90), np.int32), 10)
    out = ref.generate(np.ones((1, 90), np.int32), 4,
                       lengths=np.asarray([40]))
    assert out.tokens.shape == (1, 4)


if HAVE_HYPOTHESIS:
    _engines = {}

    def _cached_dense():
        if "dense" not in _engines:
            cfg = reduced(get_config("stablelm_3b"))
            params, _ = init_model(jax.random.PRNGKey(0), cfg)
            _engines["dense"] = (
                cfg,
                ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN,
                                 seg_len=4),
                Engine(cfg, params, max_len=MAX_LEN))
        return _engines["dense"]

    @settings(max_examples=6, deadline=None, derandomize=True,
              database=None)
    @given(st.lists(st.tuples(st.integers(4, 40), st.integers(1, 8),
                              st.booleans()),
                    min_size=1, max_size=6))
    def test_scheduler_property_any_arrival_mix(shapes):
        """Property: ANY mix of prompt lengths, generation lengths,
        sampling modes, and queue orders produces each request's exact
        static-batch tokens, and slot reuse never leaks state."""
        cfg, ce, ref = _cached_dense()
        rng = np.random.default_rng(hash(tuple(shapes)) % (2 ** 31))
        reqs = [Request(rid, rng.integers(1, cfg.vocab - 4, size=(l,))
                        .astype(np.int32), n, greedy=g, seed=rid + 1)
                for rid, (l, n, g) in enumerate(shapes)]
        got = ce.run(reqs)
        for r in reqs:
            exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                               seed=r.seed).tokens[0]
            np.testing.assert_array_equal(got[r.rid], exp,
                                          err_msg=f"rid {r.rid}")
