"""Serving chaos suite: every injected fault class — NaN logits row,
page-pool exhaustion, proposer crash, slow segment, dispatch failure —
plus deadlines, cancellation, and load shedding must leave the SURVIVING
requests bitwise token-exact vs a fault-free run (dense and paged), leak
no slot or page, and surface typed statuses.  The no-injector default is
pinned bitwise-inert: the poison mask is all-False (a ``jnp.where``
identity) and every lifecycle hook is a host-side no-op.

CI runs this file twice more than the default matrix: under forced
Pallas interpret mode and under 8 forced host devices (the sharded
resident path) — the ``chaos`` job in .github/workflows/ci.yml.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.config import ServingConfig
from repro.inference.engine import Engine
from repro.inference.faults import (FAULT_POINTS, Fault, FaultError,
                                    FaultInjector)
from repro.inference.scheduler import (STATUSES, ContinuousEngine, Request,
                                       RequestResult, summarize)
from repro.models.transformer import init_model

MAX_LEN = 96


@pytest.fixture(scope="module")
def setup(rng):
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    return cfg, params


@pytest.fixture(scope="module")
def dense(setup):
    cfg, params = setup
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4)
    ref = Engine(cfg, params, max_len=MAX_LEN)
    return cfg, params, ce, ref


@pytest.fixture(scope="module")
def paged(setup):
    cfg, params = setup
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          paged=True)
    return cfg, params, ce


def _mk(vocab, shapes, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(1, vocab - 4, size=(l,)).astype(
        np.int32), n, seed=rid * 7 + 1, **kw)
        for rid, (l, n) in enumerate(shapes)]


def _drive(ce, results, clock, max_steps=500):
    """One deterministic scheduler loop (the body of ``run`` with an
    externally controlled clock), flushing ``_pending`` at the end."""
    steps = 0
    while ce.has_work():
        assert steps < max_steps, "scheduler failed to drain"
        steps += 1
        ce.admit_ready(clock, results)
        ce.step_prefill(clock, results)
        if any(s is not None for s in ce._slot):
            ce._step_decode(clock, results)
    results.extend(ce._pending)
    ce._pending.clear()


def _assert_clean(ce):
    """No slot, reservation, group, or page survives a drained engine."""
    assert all(s is None for s in ce._slot)
    assert not ce._reserved and ce._pf is None
    assert not ce._live and not ce.queue
    if ce.paged:
        assert ce.pool.available() == ce.pool_pages - 1


# -- fault point 1: NaN logits row --------------------------------------------


@pytest.mark.parametrize("fixt", ["dense", "paged"])
def test_nan_row_fails_only_poisoned_slot(fixt, request, dense):
    """A NaN logits row fails ONLY the poisoned request (status counter
    advances, partial tokens are a strict prefix of its fault-free run)
    while every co-resident and later request stays BITWISE exact — on
    the dense and the paged resident cache."""
    cfg, _, ce_dense, ref = dense
    ce = request.getfixturevalue(fixt)[2]
    shapes = [(24, 10), (26, 12), (12, 6)]     # rid 0+1 co-resident
    ce.reset()
    base = ce.run(_mk(cfg.vocab, shapes))
    ce.reset()
    inj = FaultInjector(Fault("nan_logits", rid=1, after=1))
    ce.injector = inj
    try:
        got = ce.run(_mk(cfg.vocab, shapes))
    finally:
        ce.injector = None
    assert inj.fired == [("nan_logits", 1)]
    assert ce.stats["failed"] == 1
    # poisoned slot: tokens up to the poisoned segment, then retired
    assert 0 < len(got[1]) < len(base[1])
    np.testing.assert_array_equal(got[1], base[1][:len(got[1])])
    for rid in (0, 2):                          # survivors: bitwise intact
        np.testing.assert_array_equal(got[rid], base[rid], err_msg=f"{rid}")
    _assert_clean(ce)


def test_no_injector_is_bitwise_inert(dense):
    """The fault machinery compiled into the segment (the poison mask +
    finiteness carry) is a bitwise identity when no injector is armed:
    same tokens as the solo reference engine."""
    cfg, _, ce, ref = dense
    assert ce.injector is None
    reqs = _mk(cfg.vocab, [(20, 5), (33, 9), (7, 1), (18, 8)])
    got = ce.run(reqs)
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, greedy=r.greedy,
                           seed=r.seed).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp, err_msg=f"{r.rid}")
    _assert_clean(ce)


# -- fault point 2: page-pool exhaustion --------------------------------------


def test_pool_exhaust_transient_waits_then_serves_exact(paged, dense):
    """Transiently exhausted pool at admission: the anchor retries (well
    under admit_retries) and every request still completes ok, bitwise
    exact vs the fault-free paged run."""
    cfg, _, ce = paged
    shapes = [(24, 8), (26, 6), (12, 5)]
    ce.reset()
    base = ce.run(_mk(cfg.vocab, shapes))
    ce.reset()
    inj = FaultInjector(Fault("pool_exhaust", count=3))
    ce.injector = inj
    try:
        got = ce.run(_mk(cfg.vocab, shapes))
    finally:
        ce.injector = None
    assert len(inj.fired) == 3                  # one consult per attempt
    assert ce.stats["shed"] == 0
    for rid in got:
        np.testing.assert_array_equal(got[rid], base[rid])
    _assert_clean(ce)


def test_unfundable_anchor_sheds_after_bounded_retries(setup):
    """A persistently unfundable anchor with an otherwise-idle engine
    sheds after ``admit_retries`` attempts instead of livelocking (the
    old path requeued forever when nothing in flight could free pages)."""
    cfg, params = setup
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          paged=True, admit_retries=3)
    ce.injector = FaultInjector(Fault("pool_exhaust", count=100))
    got = ce.run(_mk(cfg.vocab, [(20, 6)]))
    ce.injector = None
    assert ce.stats["shed"] == 1
    assert got[0].size == 0                     # shed: no tokens
    assert len(ce._unfundable) == 0
    _assert_clean(ce)


# -- fault point 3: proposer crash --------------------------------------------


def test_proposer_crash_degrades_to_plain_bitwise(setup):
    """A crashing draft proposer only ever costs SPEED: spec segments
    fall back to plain fused segments (spec == plain is bitwise), and
    repeated failures trip spec_degraded so the proposer stops being
    consulted — all requests finish ok with the plain engine's tokens."""
    cfg, params = setup
    kw = dict(slots=2, max_len=MAX_LEN, seg_len=4)
    plain = ContinuousEngine(cfg, params, **kw)
    spec = ContinuousEngine(cfg, params, spec=3, **kw)
    assert spec.spec == 3
    shapes = [(24, 10), (26, 12), (12, 6)]
    base = plain.run(_mk(cfg.vocab, shapes))
    spec.injector = FaultInjector(Fault("proposer", count=100))
    got = spec.run(_mk(cfg.vocab, shapes))
    spec.injector = None
    assert spec.stats["proposer_failures"] >= 3
    h = spec.health()
    assert h["spec_degraded"] and h["proposer_failures"] >= 3
    assert "proposer" in h["last_error"]
    for rid in base:
        np.testing.assert_array_equal(got[rid], base[rid], err_msg=f"{rid}")
    _assert_clean(spec)


# -- fault point 4: slow segment (watchdog) -----------------------------------


def test_watchdog_flags_injected_slow_segment(dense):
    """A host-side segment stall past the watchdog threshold is counted
    (health: slow_segments / watchdog_slow) without touching tokens."""
    cfg, _, ce, ref = dense
    ce.reset()
    inj = FaultInjector(Fault("slow_segment", after=7, delay_s=0.75))
    ce.injector = inj
    try:
        got = ce.run(_mk(cfg.vocab, [(20, 41)]))   # 10 decode segments
    finally:
        ce.injector = None
    assert len(inj.fired) == 1
    h = ce.health()
    assert h["watchdog_slow"] >= 1 and h["slow_segments"] >= 1
    assert h["median_segment_s"] > 0.0
    exp = ref.generate(_mk(cfg.vocab, [(20, 41)])[0].prompt[None], 41,
                       seed=1).tokens[0]
    np.testing.assert_array_equal(got[0], exp)
    _assert_clean(ce)


# -- fault point 5: dispatch failure ------------------------------------------


def test_dispatch_transient_retries_exact(dense):
    """A transient dispatch failure launches nothing and touches no
    state: the segment simply retries next round and tokens stay exact."""
    cfg, _, ce, ref = dense
    ce.reset()
    reqs = _mk(cfg.vocab, [(20, 6), (33, 8)])
    inj = FaultInjector(Fault("dispatch", count=2))
    ce.injector = inj
    try:
        got = ce.run(reqs)
    finally:
        ce.injector = None
    assert len(inj.fired) == 2
    assert ce.stats["dispatch_failures"] == 2
    for r in reqs:
        exp = ref.generate(r.prompt[None], r.n_new, seed=r.seed).tokens[0]
        np.testing.assert_array_equal(got[r.rid], exp)
    _assert_clean(ce)


def test_segment_exception_scrubs_batch_and_recovers(dense):
    """An exception from the dispatched segment itself invalidates the
    DONATED caches: every in-flight request fails with its pre-segment
    partial tokens, the resident cache + pool rebuild, and the engine
    keeps serving the queue — the next request is bitwise exact."""
    cfg, _, ce, ref = dense
    ce.reset()
    orig, state = ce._segment, {"raised": False}

    def boom(*a, **k):
        if not state["raised"]:
            state["raised"] = True
            raise RuntimeError("injected device failure")
        return orig(*a, **k)

    reqs = _mk(cfg.vocab, [(24, 8), (26, 6), (12, 5)])
    results = []
    ce._segment = boom
    try:
        for r in reqs:
            ce.submit(r)
        _drive(ce, results, lambda: 0.0)
    finally:
        ce._segment = orig
    by = {r.rid: r for r in results}
    assert by[0].status == "failed" and by[1].status == "failed"
    assert ce.health()["dispatch_failures"] >= 1
    assert "injected" in ce.health()["last_error"]
    for rid in (0, 1):     # pre-segment partials: tok0 is an exact prefix
        exp = ref.generate(reqs[rid].prompt[None], reqs[rid].n_new,
                           seed=reqs[rid].seed).tokens[0]
        part = by[rid].tokens
        assert 1 <= len(part) < reqs[rid].n_new
        np.testing.assert_array_equal(part, exp[:len(part)])
    exp2 = ref.generate(reqs[2].prompt[None], reqs[2].n_new,
                        seed=reqs[2].seed).tokens[0]
    assert by[2].status == "ok"
    np.testing.assert_array_equal(by[2].tokens, exp2)
    _assert_clean(ce)


def test_warmup_raises_segment_exception(dense):
    """The same segment failure during ``warmup`` raises instead of being
    scrubbed into failed requests (a program that does not compile or fit
    would fail every request alike); after a reset the engine serves
    exactly, and scrubbing is back on for serving."""
    cfg, _, ce, ref = dense
    ce.reset()
    orig = ce._segment

    def boom(*a, **k):
        raise RuntimeError("injected compile failure")

    ce._segment = boom
    try:
        with pytest.raises(RuntimeError, match="injected compile failure"):
            ce.warmup([20])
    finally:
        ce._segment = orig
    assert not ce._warming
    ce.reset()
    r = _mk(cfg.vocab, [(20, 6)])[0]
    got = ce.run([r])
    exp = ref.generate(r.prompt[None], r.n_new, seed=r.seed).tokens[0]
    np.testing.assert_array_equal(got[r.rid], exp)
    _assert_clean(ce)


def test_serve_cli_exits_nonzero_on_failed_requests(monkeypatch):
    """``serve.py --continuous`` exits non-zero when a request ends
    ``failed``: here every segment after warmup raises."""
    from repro.launch import serve

    class Failing(ContinuousEngine):
        def serve(self, workload):
            def boom(*a, **k):
                raise RuntimeError("injected device failure")
            self._segment = boom
            return super().serve(workload)

    monkeypatch.setattr(serve, "ContinuousEngine", Failing)
    monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
    with pytest.raises(SystemExit) as exc:
        serve.main(["--arch", "stablelm_3b", "--reduced", "--continuous",
                    "--requests", "2", "--slots", "2", "--prompt-len", "16",
                    "--new-tokens", "6", "--seg-len", "4", "--rate", "1000"])
    assert exc.value.code not in (0, None)
    assert "failed" in str(exc.value.code)
    assert "injected device failure" in str(exc.value.code)


# -- lifecycle: cancellation --------------------------------------------------


def test_cancel_queued_chunking_and_resident(dense):
    """cancel() works wherever the request lives: queued (empty tokens),
    mid-chunked-admission (group shrinks, survivors unaffected), and
    resident (partial tokens, slot freed like a normal retirement);
    unknown rids return False and survivors stay bitwise exact."""
    cfg, _, ce, ref = dense
    ce.reset()
    results = []
    clock = lambda: 0.0
    reqs = _mk(cfg.vocab, [(24, 10), (26, 8), (12, 6)])
    for r in reqs:
        ce.submit(r)
    assert not ce.cancel(99)                     # unknown rid
    assert ce.cancel(2)                          # still queued
    assert not ce.cancel(2)                      # already cancelled
    ce.admit_ready(clock, results)               # rid 0+1 start chunking
    assert ce.cancel(1)                          # mid-chunked-admission
    # drive rid 0 resident, run two segments, then cancel it mid-decode
    while not any(s is not None and s.req.rid == 0 for s in ce._slot):
        ce.admit_ready(clock, results)
        ce.step_prefill(clock, results)
    ce._step_decode(clock, results)
    ce._step_decode(clock, results)
    assert ce.cancel(0)
    _drive(ce, results, clock)
    by = {r.rid: r for r in results}
    assert {by[i].status for i in (0, 1, 2)} == {"cancelled"}
    assert by[2].tokens.size == 0 and by[1].tokens.size == 0
    exp0 = ref.generate(reqs[0].prompt[None], reqs[0].n_new,
                        seed=reqs[0].seed).tokens[0]
    assert 0 < by[0].tokens.size < reqs[0].n_new     # partial prefix
    np.testing.assert_array_equal(by[0].tokens, exp0[:by[0].tokens.size])
    assert ce.stats["cancelled"] == 3
    _assert_clean(ce)


def test_cancel_resident_leaves_coresident_bitwise(dense):
    """Cancelling one resident slot mid-decode never perturbs the slot
    decoding next to it (the active-mask freeze is per-row)."""
    cfg, _, ce, ref = dense
    ce.reset()
    results = []
    clock = lambda: 0.0
    reqs = _mk(cfg.vocab, [(24, 12), (26, 12)])
    for r in reqs:
        ce.submit(r)
    while not all(s is not None for s in ce._slot):
        ce.admit_ready(clock, results)
        ce.step_prefill(clock, results)
    ce._step_decode(clock, results)
    assert ce.cancel(0)
    _drive(ce, results, clock)
    by = {r.rid: r for r in results}
    exp1 = ref.generate(reqs[1].prompt[None], reqs[1].n_new,
                        seed=reqs[1].seed).tokens[0]
    assert by[1].status == "ok"
    np.testing.assert_array_equal(by[1].tokens, exp1)
    _assert_clean(ce)


# -- lifecycle: deadlines -----------------------------------------------------


def test_deadline_expires_mid_decode_at_segment_boundary(dense):
    """A deadline-carrying request times out at a segment boundary with
    its partial tokens (an exact prefix of its unconstrained run) while
    the budgetless co-resident request finishes bitwise exact."""
    cfg, _, ce, ref = dense
    ce.reset()
    t = [0.0]
    clock = lambda: t[0]
    results = []
    reqs = _mk(cfg.vocab, [(24, 20), (26, 8)])
    reqs[0].deadline_s = 5.0
    for r in reqs:
        ce.submit(r)
    while not any(s is not None and s.req.rid == 0 for s in ce._slot):
        ce.admit_ready(clock, results)
        ce.step_prefill(clock, results)
    ce._step_decode(clock, results)              # 2 segments inside budget
    ce._step_decode(clock, results)
    t[0] = 10.0                                  # blow the budget
    _drive(ce, results, clock)
    by = {r.rid: r for r in results}
    assert by[0].status == "timeout" and by[0].deadline_s == 5.0
    exp0 = ref.generate(reqs[0].prompt[None], reqs[0].n_new,
                        seed=reqs[0].seed).tokens[0]
    assert 0 < by[0].tokens.size < reqs[0].n_new
    np.testing.assert_array_equal(by[0].tokens, exp0[:by[0].tokens.size])
    exp1 = ref.generate(reqs[1].prompt[None], reqs[1].n_new,
                        seed=reqs[1].seed).tokens[0]
    assert by[1].status == "ok"
    np.testing.assert_array_equal(by[1].tokens, exp1)
    assert ce.stats["timeout"] == 1
    _assert_clean(ce)


def test_deadline_expires_in_queue_before_admission(dense):
    """A request whose budget expires while still queued times out with
    empty tokens and never touches a slot."""
    cfg, _, ce, ref = dense
    ce.reset()
    t = [0.0]
    results = []
    reqs = _mk(cfg.vocab, [(24, 6)])
    reqs[0].deadline_s = 2.0
    ce.submit(reqs[0])
    t[0] = 3.0                                   # expire before admission
    ce.admit_ready(lambda: t[0], results)
    assert [(r.rid, r.status) for r in results] == [(0, "timeout")]
    assert results[0].tokens.size == 0
    _assert_clean(ce)


# -- lifecycle: overload shedding ---------------------------------------------


def test_queue_cap_shed_policies(dense):
    """Bounded admission queue at queue_cap: "reject" sheds arrivals,
    "oldest" sheds the longest-queued request, "lowest-priority" sheds
    the lowest-priority queued request unless the arrival is lower
    still; survivors then drain to ok results."""
    cfg, _, ce, ref = dense
    shapes = [(12, 3), (12, 3), (12, 3), (12, 3)]
    try:
        ce.queue_cap, ce.shed_policy = 2, "reject"
        for r in _mk(cfg.vocab, shapes):
            ce.submit(r)
        assert sorted(r.rid for r in ce._pending) == [2, 3]
        assert [r.rid for r in ce.queue] == [0, 1]
        got = ce.run([])                         # drain + flush pending
        assert got[2].size == 0 and got[3].size == 0
        assert got[0].size == 3 and got[1].size == 3

        ce.queue_cap, ce.shed_policy = 2, "oldest"
        for r in _mk(cfg.vocab, shapes):
            ce.submit(r)
        assert sorted(r.rid for r in ce._pending) == [0, 1]
        assert [r.rid for r in ce.queue] == [2, 3]
        ce.run([])

        ce.queue_cap, ce.shed_policy = 2, "lowest-priority"
        reqs = _mk(cfg.vocab, shapes)
        for rid, pr in enumerate((1, 0, 2, 0)):
            reqs[rid].priority = pr
        for r in reqs:
            ce.submit(r)
        # rid 2 (pr 2) sheds queued rid 1 (pr 0); rid 3 (pr 0) sheds itself
        assert sorted(r.rid for r in ce._pending) == [1, 3]
        assert [r.rid for r in ce.queue] == [0, 2]
        ce.run([])
        assert ce.stats["shed"] >= 6
    finally:
        ce.queue_cap, ce.shed_policy = None, "reject"
    _assert_clean(ce)


# -- validation: duplicate rids + empty prompts -------------------------------


def test_duplicate_rid_and_empty_prompt_rejected(dense):
    cfg, _, ce, ref = dense
    prompt = _mk(cfg.vocab, [(12, 3)])[0].prompt
    ce.submit(Request(7, prompt, 3))
    with pytest.raises(ValueError, match="already in flight"):
        ce.submit(Request(7, prompt, 4))
    got = ce.run([])                             # retires rid 7
    assert got[7].size == 3
    ce.submit(Request(7, prompt, 3))             # rid reusable after emit
    assert ce.run([])[7].size == 3
    with pytest.raises(ValueError, match="empty prompt"):
        ce.submit(Request(8, np.zeros((0,), np.int32), 4))
    with pytest.raises(ValueError, match="empty prompt"):
        ref.generate(np.zeros((1, 0), np.int32), 4)
    with pytest.raises(ValueError, match="empty prompt"):
        ref.generate(np.ones((2, 8), np.int32), 4,
                     lengths=np.asarray([8, 0], np.int32))
    _assert_clean(ce)


# -- config surface -----------------------------------------------------------


def test_fault_fields_config_equals_kwargs_bitwise(setup):
    """The PR's new knobs keep the ServingConfig contract: the kwargs
    form and the config form build engines with identical behavior, and
    invalid values raise at construction."""
    cfg, params = setup
    kw = dict(slots=2, max_len=MAX_LEN, seg_len=4, queue_cap=8,
              shed_policy="oldest", deadline_s=30.0, admit_retries=4)
    a = ContinuousEngine(cfg, params, **kw)
    b = ContinuousEngine(cfg, params, config=ServingConfig(**kw))
    for e in (a, b):
        assert (e.queue_cap, e.shed_policy, e.deadline_s,
                e.admit_retries) == (8, "oldest", 30.0, 4)
    shapes = [(20, 5), (33, 7)]
    ga = a.run(_mk(cfg.vocab, shapes))
    gb = b.run(_mk(cfg.vocab, shapes))
    for rid in ga:
        np.testing.assert_array_equal(ga[rid], gb[rid])
    with pytest.raises(ValueError, match="shed_policy"):
        ServingConfig(shed_policy="drop-newest")
    with pytest.raises(ValueError, match="queue_cap"):
        ServingConfig(queue_cap=0)
    with pytest.raises(ValueError, match="not a known fault point"):
        Fault("gamma_ray")
    assert set(FAULT_POINTS) == {"nan_logits", "pool_exhaust", "proposer",
                                 "slow_segment", "dispatch"}
    assert issubclass(FaultError, RuntimeError)


def test_health_and_summarize_surface_statuses(dense):
    """health() reports occupancy + failure counters; summarize() counts
    every status and computes SLO attainment over completed
    deadline-carrying results only."""
    cfg, _, ce, _ = dense
    h = ce.health()
    for k in ("resident", "queued", "reserved", "chunking", "pool_free",
              "segments", "median_segment_s", "slow_segments",
              "watchdog_slow", "dispatch_failures", "proposer_failures",
              "spec_degraded", "failed", "shed", "cancelled", "timeout",
              "last_error"):
        assert k in h, k
    tok = np.arange(4, dtype=np.int32)
    rr = lambda rid, st, fin, dl: RequestResult(
        rid, tok, 8, 4, 0.0, 0.1, fin, status=st, deadline_s=dl)
    res = [rr(0, "ok", 1.0, 2.0),      # within budget
           rr(1, "ok", 9.0, 2.0),      # completed but blew the budget
           rr(2, "ok", 1.0, None),     # budgetless: excluded from SLO
           rr(3, "timeout", 2.0, 2.0),
           rr(4, "shed", 0.0, None)]
    s = summarize(res, 10.0)
    assert (s["n_ok"], s["n_timeout"], s["n_shed"],
            s["n_cancelled"], s["n_failed"]) == (3, 1, 1, 0, 0)
    assert s["n_requests"] == 5 and s["delivered_tokens"] == 12
    assert s["slo_attainment"] == 0.5
    assert set(f"n_{x}" for x in STATUSES) <= set(s)
    empty = summarize([], 0.0)
    assert empty["slo_attainment"] == 1.0 and empty["n_ok"] == 0


# -- sharded resident path ----------------------------------------------------


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")
def test_sharded_nan_isolation_matches_unsharded(dense):
    """Fault isolation holds on the mesh-sharded resident engine: the
    poisoned slot fails, survivors stay bitwise equal to the unsharded
    fault-free run."""
    from repro.launch.mesh import make_serving_mesh
    cfg, params, ce, _ = dense
    shapes = [(24, 10), (26, 12), (12, 6)]
    ce.reset()
    base = ce.run(_mk(cfg.vocab, shapes))
    sh = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          mesh=make_serving_mesh(2))
    sh.injector = FaultInjector(Fault("nan_logits", rid=1, after=1))
    got = sh.run(_mk(cfg.vocab, shapes))
    sh.injector = None
    assert sh.stats["failed"] == 1
    assert 0 < len(got[1]) < len(base[1])
    np.testing.assert_array_equal(got[1], base[1][:len(got[1])])
    for rid in (0, 2):
        np.testing.assert_array_equal(got[rid], base[rid], err_msg=f"{rid}")
    _assert_clean(sh)
