"""Continuous-batching serving layer over the fused decode fast path.

The static ``Engine.generate`` runs ONE fixed batch end-to-end: every slot
waits for the longest request, and a new batch cannot start until the whole
previous one retires.  This module keeps a single RESIDENT engine of
``slots`` cache rows alive instead and streams requests through it:

  request queue   FIFO of submitted requests (an open-loop arrival process
                  in serving benchmarks); admission requires
                  prompt_len + n_new <= max_len.
  slot map        per-slot host state (request id, tokens collected,
                  remaining budget) mirroring the device-side carries.
  segments        decode runs in fixed-size jitted segments of ``seg_len``
                  fused scan steps over ALL slots (active or not).  Between
                  segments, finished sequences retire and queued requests
                  are admitted into freed slots.  The segment shape never
                  changes, so the generation scan COMPILES EXACTLY ONCE
                  (per dsa_mode in use — see per-request overrides below).
  admission       DEFAULT (chunked): an admission group's prompts stream
                  through a bucket-sized STAGING cache in fixed-size
                  chunk-steps (transformer.chunk_step), and the serving
                  loop alternates stall-bounded chunk BURSTS (roughly one
                  segment's worth of chunk compute, self-tuned from the
                  running timings; the whole tail when no decoder is
                  resident) with decode segments, so decoders keep
                  producing tokens while a long prompt is ingested;
                  chunking also stops at the last real chunk instead of
                  computing the full padded bucket.  Each request's first
                  token is sampled from its final chunk's logits row with
                  its own PRNG chain, and its staging row is inserted into
                  its reserved slot IMMEDIATELY (zero-extend + full-slot
                  overwrite, so a slot can never leak KV/kt/ktb state from
                  a previous tenant) — it decodes in the next segment even
                  while co-admitted longer prompts are still chunking.
                  LEGACY (blocking, ``chunked_prefill=False`` or archs
                  where chunk steps aren't token-exact —
                  engine.can_chunk_prefill): the whole padded prompt runs
                  in one Engine.prefill call while every resident decoder
                  stalls.
  per-slot state  models/attention keeps ``pos`` per slot and takes an
                  ``active`` mask: inactive slots freeze their cache, drop
                  their writes, and attend with kv_len = 0.
  per-request     ``Request.temperature`` scales that request's sampled
                  logits (greedy/seed were already per request), and
                  ``Request.dsa_mode`` overrides the engine's DSA decode
                  path.  Modes are STATIC code paths, so segments are
                  mode-affine: one segment runs one dsa_mode, admission
                  only co-schedules same-mode requests, and the engine
                  switches modes when it drains idle (one extra segment /
                  prefill compile per distinct mode used).

Mesh sharding (``mesh=``): the resident cache and every per-slot carry
shard over the mesh's "data" axis
(distributed.sharding.make_serving_rules), so segments, chunked
admission, and speculative verify run as ONE SPMD program per host group.
On a 1-D ("data",) mesh weights are replicated and each slot's row is
computed whole on one shard; on a 2-D ("data", "model") mesh weights
ADDITIONALLY shard over "model" (tensor parallelism: Q/K/V/O over heads,
MLP/experts, vocab) with the resident KV cache and its quant scales
head-sharded alongside, GSPMD inserting one all-reduce after each
contracting matmul.  Both stay token-exact vs mesh=None at the same
seeds/temps/dsa_mode — the reduction order is fixed per mesh
(tests/test_multidevice.py, CI's forced-host-device multi-device job).
The DSA kt/ktb score caches stay replicated over "model", so every shard
selects IDENTICAL top-k blocks and attends on its own heads locally.

Token-exactness: a request served here produces exactly the tokens of
``Engine(cfg, params, max_len=<same>).generate(prompt[None], n_new,
temperature=..., dsa_mode=...)`` at the same seed — chunked admission
reproduces the bucketed whole-prompt prefill bitwise (same geometry: the
staging cache IS the prompt bucket), the per-slot sampling chain replays
Engine's B=1 key chain, and DSA block selection sees the same cache
geometry (selection top-k depends on max_len, so the equivalence requires
equal ``max_len``).  Pinned by tests/test_scheduler.py.

Recompilation contract: one compile per prompt bucket for the chunk step
and its staging-cache build (at admission widths 1 and ``slots``), slot
insertion, and the legacy prefill; one compile total for the decode
segment.  Per-request dsa_mode overrides add one compile per DISTINCT
MODE actually used for the segment/chunk/prefill programs.  Nothing
recompiles per request, per n_new, per temperature, per arrival
pattern, or per burst size.
``warmup`` precompiles the fixed chunk-shape set for its prompt buckets.

Fault tolerance: every request retires with a typed ``RequestResult.status``
(``ok | timeout | cancelled | failed | shed``).  Deadlines
(``Request.deadline_s`` / ``ServingConfig.deadline_s``) and ``cancel(rid)``
retire queued, chunking, or resident requests at segment boundaries —
a resident slot freezes via the existing ``active`` mask and returns its
pages exactly like a normal retirement, so co-resident slots' tokens are
bitwise untouched.  Overload sheds at a bounded admission queue
(``queue_cap`` + ``shed_policy``), unfundable paged anchors retry with
backoff instead of livelocking, a non-finite logits row fails ONLY its
slot, a crashing draft proposer degrades speculative segments to plain
decode (same tokens), a ``StepWatchdog`` flags slow segments, and a real
device-side segment failure fails the in-flight batch, rebuilds the
resident cache, and keeps serving the queue (``health()`` snapshots all
of it).  With no deadlines, no queue bound, and no ``FaultInjector``
armed, every path above is bitwise inert (pinned by tests/test_faults.py).

Observability: every phase of the serving loop runs inside a host span,
``inference.telemetry.span(tel, name)``: ``serve.admit`` (``admit_ready``:
grouping, page allocation, prefix lookup) with ``serve.admit.staging``
(the chunked group's staging-cache build) or ``serve.admit.blocking``
(the legacy whole-prompt admission); ``serve.chunk_burst``
(``step_prefill``) with ``serve.insert`` (a finishing member's slot insert
and activation); ``serve.segment`` (a decode or speculative segment) with
``serve.segment.dispatch`` (argument copies and the jitted call),
``serve.segment.wait`` (the host copies that block on the device) and
``serve.segment.emit`` (per-slot emission); ``serve.wait_arrival`` (idle
sleeps in ``serve``).  A span is always a
``jax.profiler.TraceAnnotation``, so a profiler trace shows each phase on
the device trace's clock (``time.time_ns``); with no profiler running it
costs a flag check.
``RequestResult.admit_s`` is when the request's admission group started,
so queue wait is ``admit_s - arrival_s`` and admission to first token is
``first_token_s - admit_s``.

``ServingConfig.telemetry`` (inference.telemetry.Telemetry) adds request
spans and records every phase span in a Chrome-trace ring on the same
clock, a Prometheus metrics registry fed from the same ``_emit``/
``health()`` surfaces (the three can never disagree), a compile-event
watcher that makes the recompilation contract above a live,
CI-assertable metric, and a sampled DSA block-selection probe
(``_sparsity_probe``).  ``telemetry=None`` (default) is bitwise-inert —
no wrapper, no hook, no extra dispatch (pinned by tests/test_telemetry.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.fault_tolerance import StepWatchdog
from repro.distributed.sharding import is_spec_leaf, shard, tree_specs
from repro.inference.config import ServingConfig, resolve_config
from repro.inference.engine import Engine, _ro_view, _sample, \
    can_chunk_prefill, can_page, pow2_bucket
from repro.inference.faults import FaultError
from repro.inference.speculative import NGramProposer, SpeculativeDecoder, \
    can_speculate
from repro.inference.telemetry import span
from repro.models.attention import DSA_MODES, cache_page_size
from repro.models.transformer import chunk_step, decode_step, init_cache, \
    unstack_group_caches, unstacked_cache_specs

# cache leaves with a per-token row axis right after the batch axis; their
# slot row is zero-extended from the prefill bucket to the resident length
# at insertion (everything beyond the prefill is wiped)
_SEQ_KEYS = {"k", "v", "kt", "ktb", "c_kv", "k_rope",
             "k_s", "v_s", "kt_s", "ktb_s"}



@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (L,) int32
    n_new: int
    greedy: bool = True
    seed: int = 0
    arrival_s: float = 0.0        # offset from serve() start (open loop)
    temperature: float = 1.0      # sampled (non-greedy) logit scale
    dsa_mode: Optional[str] = None  # override the engine's DSA decode path
    # copy-on-write prefix sharing (paged engines): the first prefix_len
    # prompt tokens are a common prefix shared with other requests carrying
    # the same prefix_key — they map the same physical cache pages and skip
    # re-prefilling the shared part.  submit() hashes the prefix tokens
    # when the key is left None, so equal declared prefixes always match.
    prefix_len: int = 0
    prefix_key: Optional[str] = None
    # lifecycle: latency budget in seconds since arrival (None = the
    # engine's ServingConfig.deadline_s, which defaults to none), and the
    # shedding priority under overload (higher survives "lowest-priority")
    deadline_s: Optional[float] = None
    priority: int = 0

    def __post_init__(self):
        if self.dsa_mode is not None and self.dsa_mode not in DSA_MODES:
            raise ValueError(
                f"Request.dsa_mode={self.dsa_mode!r} is not a valid DSA "
                f"mode; valid: {DSA_MODES} (or None for the engine default)")


# the typed retirement statuses: "ok" delivered all n_new tokens; the rest
# surface partial (timeout/cancelled/failed: whatever was collected before
# the slot froze) or empty (shed, never admitted) token arrays
STATUSES = ("ok", "timeout", "cancelled", "failed", "shed")


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray            # (n_new,) when status == "ok", else fewer
    prompt_len: int
    n_new: int
    arrival_s: float
    admit_s: float
    finish_s: float
    first_token_s: float = 0.0    # when token 0 was sampled (TTFT anchor)
    status: str = "ok"            # one of STATUSES
    deadline_s: Optional[float] = None   # effective budget (SLO accounting)

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s

    @property
    def ttft_s(self) -> float:
        return self.first_token_s - self.arrival_s


@dataclasses.dataclass
class _SlotState:
    req: Request
    tok0: int
    collected: List[np.ndarray]
    remaining: int
    admit_s: float
    first_token_s: float = 0.0
    # incremental token history (prompt + tok0 + every collected token),
    # appended as segments collect — draft proposers read a VIEW of it per
    # verify round (O(new tokens) host work) instead of re-concatenating
    # the full context (O(T) per round, O(T^2) over a generation)
    history: Optional[np.ndarray] = None
    hist_len: int = 0

    def extend_history(self, toks: np.ndarray) -> None:
        n = toks.shape[0]
        self.history[self.hist_len:self.hist_len + n] = toks
        self.hist_len += n


@dataclasses.dataclass
class _PrefillGroup:
    """An in-flight chunked admission: one same-bucket same-mode group
    streaming through a bucket-sized staging cache, one chunk per serving
    iteration."""
    reqs: List[Request]
    slots: List[Optional[int]]    # reserved resident slot per member
    bucket: int
    chunk: int                    # chunk width (min(chunk_tokens, bucket))
    mode: str                     # effective dsa_mode
    caches: object                # staging cache (unstacked, bpf rows)
    lengths: np.ndarray           # (bpf,) true prompt length per row
    j: int = 0                    # next chunk index
    n_chunks: int = 0
    mat: Optional[np.ndarray] = None   # (bpf, n_chunks*chunk) padded tokens
    tbls: Optional[List] = None   # paged: per-member page-table row (or None)
    dead: Set[int] = dataclasses.field(default_factory=set)
    # member indices cancelled/expired mid-chunk: their rows keep chunking
    # (the group geometry is fixed) but they never activate or emit
    admit_s: float = 0.0          # serve clock when the group started


def _leaf_name(path) -> Optional[str]:
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            return k.key
    return None


class PagePool:
    """Host-side accounting of a PAGED resident cache's physical pages.

    The device side is a flat pool of ``n_pages`` pages of ``page_rows``
    cache rows each, indirected per slot through ``page_tbl``
    (models.attention init_cache_attention); this mirror decides which
    pages back which slot.  Page 0 is the permanent ZERO page — never
    allocated — so unmapped table entries read zero rows.

    Invariant (pinned by tests/test_property.py): every page in
    [1, n_pages) is EITHER on the free stack OR has refcount > 0, never
    both — retire/readmit churn can neither leak nor double-free pages.
    Refcounts exceed 1 only for copy-on-write shared prefix pages: the
    prefix registry holds one reference and every slot mapping the prefix
    holds another, so a retiring slot returns exactly its non-shared
    pages and a registered prefix survives its readers.

    Pages freed with data in them land in ``dirty`` and are zeroed on
    device before their next mapping (``take_dirty``) — a freshly mapped
    page always reads as zeros, which is what keeps the paged cache's
    gathered logical view byte-identical to a dense zero-initialized
    cache."""

    def __init__(self, n_pages: int, page_rows: int):
        assert n_pages >= 2, n_pages    # the zero page + at least one real
        self.n_pages = n_pages
        self.page_rows = page_rows
        self.free: List[int] = list(range(n_pages - 1, 0, -1))
        self.ref = np.zeros((n_pages,), np.int32)
        self.slot_pages: Dict[int, Tuple[List[int], int]] = {}
        self.dirty: Set[int] = set()
        # LRU copy-on-write prefix registry:
        # (prefix_key, prefix_len, bucket, mode) -> shared pages
        self.prefixes: "OrderedDict[tuple, List[int]]" = OrderedDict()

    def available(self) -> int:
        return len(self.free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self.free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, have {len(self.free)} "
                f"(admission accounting should have prevented this)")
        pages = [self.free.pop() for _ in range(n)]
        for p in pages:
            self.ref[p] = 1
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        for p in pages:
            self.ref[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        for p in pages:
            self.ref[p] -= 1
            assert self.ref[p] >= 0, f"page {p} over-released"
            if self.ref[p] == 0:
                self.free.append(p)
                self.dirty.add(p)

    def assign_slot(self, slot: int, pages: Sequence[int],
                    n_shared: int) -> None:
        self.slot_pages[slot] = (list(pages), n_shared)

    def free_slot(self, slot: int) -> None:
        pages, _ = self.slot_pages.pop(slot)
        self.release(pages)

    def take_dirty(self, pages: Sequence[int]) -> List[int]:
        """The subset of ``pages`` needing a device zero before use (freed
        with stale rows since their last mapping); marks them clean."""
        d = [p for p in pages if p in self.dirty]
        self.dirty.difference_update(d)
        return d

    # -- copy-on-write prefix registry (LRU) --------------------------------

    def lookup_prefix(self, key) -> Optional[List[int]]:
        pages = self.prefixes.get(key)
        if pages is not None:
            self.prefixes.move_to_end(key)     # LRU refresh
        return pages

    def register_prefix(self, key, pages: Sequence[int]) -> None:
        """The registry takes ownership of alloc()'s reference."""
        self.prefixes[key] = list(pages)

    def evict_for(self, n: int, keep=None) -> None:
        """LRU-evict prefix registrations until ``n`` pages are free (or
        nothing evictable is left).  Evicted pages still mapped by live
        slots free later, at those slots' retirement."""
        while len(self.free) < n:
            key = next((k for k in self.prefixes if k != keep), None)
            if key is None:
                return
            self.release(self.prefixes.pop(key))


class ContinuousEngine:
    """Resident continuous-batching engine (see module docstring)."""

    _warming = False          # True inside warmup(): dispatch failures raise

    def __init__(self, cfg: ArchConfig, params, *,
                 config: Optional[ServingConfig] = None, **kw):
        """Build from a ``ServingConfig`` (``config=...``) or from the
        legacy keyword arguments (``slots=``, ``max_len=``, ...), which are
        forwarded into the config field-by-field — bitwise-identical
        behavior either way.  New call sites should pass a config; the
        kwargs form is kept for compatibility (deprecated, not removed)."""
        c = resolve_config(config, kw)
        self.config = c
        self.cfg = cfg
        self.slots = slots = c.slots
        self.max_len = max_len = c.max_len
        self.seg_len = seg_len = c.seg_len
        dsa_mode, long_context, paged = c.dsa_mode, c.long_context, c.paged
        # mesh-sharded resident serving: the (slots, max_len) cache and
        # every per-slot carry shard over the mesh's "data" axis, so
        # segments/chunks/verifies run as ONE SPMD program per host group
        # — and stay token-exact vs mesh=None (pinned by
        # tests/test_multidevice.py).  Weights replicate on a dp-only
        # mesh; a ("data", "model") mesh tensor-parallel-shards them (and
        # the cache's head axes) over "model" — see Engine.__init__.
        # Slots not divisible by the data axis simply resolve to
        # replicated (graceful, not an error).
        self.mesh = c.mesh
        # prefill machinery + flags are shared with the static engine so the
        # scheduler is token-exact against Engine.generate per request
        self.engine = Engine(cfg, params, config=c, loop="scan")
        # chunked admission is the default wherever it is token-exact; the
        # legacy whole-prompt blocking prefill stays for ssm/swa/enc-dec
        # (where bucketing already auto-disables) and vision archs; MoE
        # archs chunk-admit when moe_prefill="dense" routes their prefill
        # through the decode-dense expert path
        chunk_ok = self.engine.bucket_prompts and can_chunk_prefill(
            cfg, dsa_mode, moe_dense=self.engine.moe_dense)
        self.chunked = chunk_ok if c.chunked_prefill is None else (
            c.chunked_prefill and chunk_ok)
        # PAGED resident cache (the perf tentpole): per-slot dense rows are
        # replaced by a block-table indirection over one shared physical
        # page pool (page size = the DSA block_k, so logical selection
        # blocks ARE pages), with host-side accounting in PagePool and
        # copy-on-write prefix sharing across requests that declare a
        # common prefix.  Decode/insert writes translate through page_tbl
        # and the read paths gather logical views, so paged serving stays
        # BITWISE token-exact vs the dense layout at the same geometry.
        self.paged = paged
        if paged:
            if not can_page(cfg):
                raise ValueError(
                    f"paged=True: {cfg.name} is outside the paging envelope "
                    f"(needs a pure-attention decoder: no ssm/rwkv/swa/mla/"
                    f"enc-dec/cross-attn)")
            self._page_rows = cache_page_size(cfg, self.engine.decode_flags)
            dsa_dec = (cfg.dsa.enabled and long_context
                       and not cfg.swa_window)
            if max_len % self._page_rows and not dsa_dec:
                raise ValueError(
                    f"paged=True needs max_len divisible by the page size "
                    f"({self._page_rows}); got {max_len}")
            self._n_kb = -(-max_len // self._page_rows)
            # default pool: every slot can hold a full max_len sequence
            # (parity with the dense layout) + the permanent zero page;
            # smaller pools trade capacity for memory and rely on
            # admission accounting to refuse what they can't back
            self.pool_pages = (c.pool_pages if c.pool_pages is not None
                               else slots * self._n_kb + 1)
        else:
            self.pool_pages = 0
        # speculative decode segments (draft-and-verify): auto-off outside
        # the speculation envelope, mirroring chunked admission; the paged
        # cache keeps verify on the dense staging path only, so spec and
        # paged are mutually exclusive for now
        self.spec = c.spec if (c.spec and not paged
                               and can_speculate(cfg, dsa_mode, c.spec)
                               ) else 0
        self.draft = c.draft if c.draft is not None else (
            NGramProposer() if self.spec else None)
        # rounds per speculative segment: sized so a fully-accepted spec
        # segment emits about one plain segment's worth of tokens
        self.spec_rounds = (c.spec_rounds if c.spec_rounds is not None
                            else max(1, seg_len // (self.spec + 1))
                            ) if self.spec else 0
        self._spec = SpeculativeDecoder(
            cfg, self.spec, telemetry=c.telemetry) if self.spec else None
        # mode-affine starvation aging: a queued request whose dsa_mode
        # can't join the current segments forces a drain/mode-switch once
        # it has waited this long (None = wait for a natural idle drain)
        self.max_mode_wait_s = c.max_mode_wait_s
        # fault tolerance: bounded admission queue + shed policy, default
        # latency budget, unfundable-anchor retry bound, fault injector
        # (public and mutable — it never participates in compilation, so
        # tests swap it between runs on one engine)
        self.queue_cap = c.queue_cap
        self.shed_policy = c.shed_policy
        self.deadline_s = c.deadline_s
        self.admit_retries = c.admit_retries
        self.injector = c.injector
        # chunk width: pow2, and block-aligned so chunk widths/starts stay
        # block_q/block_k multiples on the DSA paths (a chunk wider than a
        # small prompt bucket is fine: the overhang rows drop out of
        # bounds, the geometry stays the bucket's)
        self._chunk_floor = 16
        if cfg.dsa.enabled:
            self._chunk_floor = max(self._chunk_floor, cfg.dsa.block_q,
                                    cfg.dsa.block_k)
        self.chunk_tokens = pow2_bucket(c.chunk_tokens, self._chunk_floor)

        # logical axes of the unstacked cache leaves by NAME, recorded
        # from the real spec tree at reset() (single source of truth:
        # attention.cache_specs_* via transformer.unstacked_cache_specs).
        # The slot-insert pins its outputs to these so insert and segment
        # dispatches agree on ONE cache sharding — otherwise the decode
        # segment compiles once per producer; unknown leaves fall back to
        # batch-axis-0 only
        self._cache_logical: Dict[str, tuple] = {}

        def _pin_cache_leaf(name, x):
            log = self._cache_logical.get(
                name, ("batch",) + (None,) * (x.ndim - 1))
            return shard(x, *log[:x.ndim])

        def _insert_fn(resident, pre, slot, row):
            """Overwrite resident slot ``slot`` with row ``row`` of a
            bucket-sized prefill cache, zero-extending per-token rows —
            the in-place slot reset."""
            def one(path, res, p):
                name = _leaf_name(path)
                leaf = p[row].astype(res.dtype)
                if name in _SEQ_KEYS and res.shape[1] != p.shape[1]:
                    full = jnp.zeros(res.shape[1:], res.dtype)
                    leaf = jax.lax.dynamic_update_slice(
                        full, leaf, (0,) * leaf.ndim)
                return _pin_cache_leaf(name, res.at[slot].set(leaf))
            return jax.tree_util.tree_map_with_path(one, resident, pre)

        def _segment_fn(params, tok, caches, keys, active, greedy, temps,
                        remaining, poison, flags):
            """seg_len fused decode steps over all slots; inactive slots
            freeze.  Mirrors Engine._decode_loop's body per active row,
            with a per-slot PRNG chain (split + categorical per row) and
            per-slot sampling temperatures (1.0 divides exactly, so the
            default is bit-identical to the unscaled chain).

            ``poison`` (traced, normally all-False — an elementwise select
            with a False mask is a bitwise identity, so the fault plumbing
            keeps the one-compile contract) NaNs a slot's logits row, and
            the ``finite`` carry records per-slot whether every ACTIVE
            step's logits row stayed finite — the host fails non-finite
            slots after the segment (fault isolation: only the poisoned
            row's own sampling consumes its logits, so co-resident slots
            are untouched)."""
            def body(carry, _):
                tok, caches, keys, active, remaining, finite = carry
                logits, caches = decode_step(params, cfg, flags, tok,
                                             caches, active=active)
                with jax.named_scope("logits_sample"):
                    lg = logits[:, -1]
                    lg = jnp.where(poison[:, None],
                                   jnp.full_like(lg, jnp.nan), lg)
                    finite = finite & (~active
                                       | jnp.all(jnp.isfinite(lg), -1))
                    # rows shard over "data", vocab REPLICATED per row: each
                    # per-slot draw runs over its whole row locally, as in
                    # the unsharded program (no-op without a mesh)
                    lg = shard(lg, "batch", None)
                    ks = jax.vmap(jax.random.split)(keys)     # (B, 2, 2)
                    nxt_s = jax.vmap(jax.random.categorical)(
                        ks[:, 1], lg / temps[:, None])
                    nxt_g = jnp.argmax(lg, -1)
                    nxt = jnp.where(greedy, nxt_g, nxt_s).astype(jnp.int32)
                    keys = jnp.where(greedy[:, None], keys, ks[:, 0])
                    nxt = jnp.where(active, nxt, tok[:, 0])[:, None]
                    remaining = remaining - active.astype(jnp.int32)
                    active = active & (remaining > 0)
                return (nxt, caches, keys, active, remaining, finite), \
                    nxt[:, 0]

            carry, toks = jax.lax.scan(
                body, (tok, caches, keys, active, remaining,
                       jnp.ones_like(active)), None, length=seg_len)
            tok, caches, keys, active, remaining, finite = carry
            return (tok, caches, keys, active, remaining, finite,
                    toks.swapaxes(0, 1))

        def _chunk_fn(params, caches, toks, chunk_len, active, flags,
                      sel_len):
            """One chunk-step of admission prefill over the staging cache;
            returns each row's logits at its last real chunk token (the
            prefill-logits row when the chunk is the prompt's last).
            ``sel_len`` is the prompt bucket — the selection/attention
            geometry (the physical DSA cache may be block-rounded wider)."""
            logits, caches = chunk_step(params, cfg, flags, toks, caches,
                                        chunk_len, active=active,
                                        sel_len=sel_len)
            with jax.named_scope("logits_sample"):
                idx = (jnp.maximum(chunk_len, 1) - 1)[:, None, None]
                last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
            return last, caches

        # paged twins of the insert + slot-reset machinery.  Staging caches
        # are DENSE (no page_tbl leaf), so the trees differ in structure —
        # the staging tree is flattened into a by-path dict and the map
        # runs over the resident tree alone.
        bkp = self._page_rows if paged else 1
        nrows_pool = self.pool_pages * bkp

        def _insert_paged_fn(resident, pre, slot, row, tbl_row):
            """Paged slot insert: scatter row ``row`` of a bucket-sized
            dense staging cache into the pages ``tbl_row`` maps and install
            the page-table row.  Staged rows whose logical block is
            unmapped (table entry 0 — beyond this slot's allocation) drop
            out of bounds; freshly mapped pages were zeroed at allocation,
            so the slot's gathered logical view is byte-identical to the
            dense zero-extended insert."""
            pre_by = {jax.tree_util.keystr(p): v for p, v in
                      jax.tree_util.tree_flatten_with_path(pre)[0]}

            def one(path, res):
                name = _leaf_name(path)
                if name == "page_tbl":
                    return _pin_cache_leaf(name, res.at[slot].set(tbl_row))
                leaf = pre_by[jax.tree_util.keystr(path)][row]
                if name in ("k", "v", "kt", "k_s", "v_s", "kt_s"):
                    r = jnp.arange(leaf.shape[0])
                    pg = tbl_row[r // bkp]
                    flat = jnp.where(pg > 0, pg * bkp + r % bkp, nrows_pool)
                    return _pin_cache_leaf(name, res.at[flat].set(
                        leaf.astype(res.dtype), mode="drop"))
                if name in ("ktb", "ktb_s"):
                    pgs = tbl_row[:leaf.shape[0]]
                    tgt = jnp.where(pgs > 0, pgs, self.pool_pages)
                    return _pin_cache_leaf(name, res.at[tgt].set(
                        leaf.astype(res.dtype), mode="drop"))
                return _pin_cache_leaf(name, res.at[slot].set(
                    leaf.astype(res.dtype)))
            return jax.tree_util.tree_map_with_path(one, resident)

        def _zero_pages_fn(resident, ids):
            """Zero pool pages ``ids`` in every pool leaf — run on dirty
            pages at mapping time so a freshly mapped page always reads as
            zeros.  ``ids`` is 0-padded to a bucketed width (page 0 is the
            permanent zero page, so zeroing it is a no-op by value)."""
            rows = (ids[:, None] * bkp
                    + jnp.arange(bkp)[None, :]).reshape(-1)

            def one(path, res):
                name = _leaf_name(path)
                if name in ("k", "v", "kt", "k_s", "v_s", "kt_s"):
                    return _pin_cache_leaf(name, res.at[rows].set(
                        jnp.zeros((), res.dtype)))
                if name in ("ktb", "ktb_s"):
                    return _pin_cache_leaf(name, res.at[ids].set(
                        jnp.zeros((), res.dtype)))
                return res
            return jax.tree_util.tree_map_with_path(one, resident)

        def _seed_fn(staging, resident, pages, r_rows):
            """Seed a staging cache's first ``r_rows`` rows from the pool's
            shared-prefix ``pages`` (a prefix-registry HIT): reproduces the
            staging state after chunking rows [0, r_rows) — exactly the
            chunks the group then skips.  r_rows is a whole number of
            pages (static: it slices)."""
            res_by = {jax.tree_util.keystr(p): v for p, v in
                      jax.tree_util.tree_flatten_with_path(resident)[0]}

            def one(path, st):
                name = _leaf_name(path)
                if name not in ("k", "v", "kt", "ktb", "pos",
                                "k_s", "v_s", "kt_s", "ktb_s"):
                    return st
                if name == "pos":
                    return jnp.full_like(st, r_rows)
                src = res_by[jax.tree_util.keystr(path)]
                if name in ("ktb", "ktb_s"):
                    return st.at[:, :pages.shape[0]].set(
                        src[pages][None].astype(st.dtype))
                rows = (pages[:, None] * bkp
                        + jnp.arange(bkp)[None, :]).reshape(-1)
                return st.at[:, :r_rows].set(
                    src[rows][None].astype(st.dtype))
            return jax.tree_util.tree_map_with_path(one, staging)

        self._insert = jax.jit(_insert_fn, donate_argnums=(0,))
        self._insert_paged = jax.jit(_insert_paged_fn, donate_argnums=(0,))
        self._zero_pages = jax.jit(_zero_pages_fn, donate_argnums=(0,))
        self._seed = jax.jit(_seed_fn, static_argnames=("r_rows",),
                             donate_argnums=(0,))
        self._segment = jax.jit(_segment_fn, static_argnames=("flags",),
                                donate_argnums=(2,))
        self._chunk = jax.jit(_chunk_fn,
                              static_argnames=("flags", "sel_len"),
                              donate_argnums=(1,))

        # observability (inference.telemetry): telemetry=None (default) is
        # bitwise-inert — no wrapper, no hook, no extra dispatch.  With a
        # Telemetry bound, every jitted entry point gains a host-side
        # compile watcher (the engine's own prefill/decode jits were
        # wrapped in Engine.__init__ from the same config), the request
        # lifecycle and segment/chunk/fault events land on a trace
        # timeline, and once per ``sample_every`` segments a sel_probe
        # replay samples the DSA block selection (see _sparsity_probe).
        self._builders: Dict[tuple, object] = {}  # _new_cache's programs
        self._staging = self._new_cache
        self.telemetry = c.telemetry
        self._probe = None              # lazily-built sparsity probe jit
        self._probe_prev: Dict[int, tuple] = {}   # slot -> (rid, blocks)
        if self.telemetry is not None:
            tel = self.telemetry
            tel.bind_engine(self)
            self._insert = tel.wrap_jit("insert", self._insert)
            self._insert_paged = tel.wrap_jit("insert_paged",
                                              self._insert_paged)
            self._zero_pages = tel.wrap_jit("zero_pages", self._zero_pages)
            self._seed = tel.wrap_jit("seed", self._seed)
            self._segment = tel.wrap_jit("segment", self._segment)
            self._chunk = tel.wrap_jit("chunk", self._chunk)
            self._staging = tel.wrap_jit("staging", self._staging)

        self.queue: deque = deque()
        self.reset()     # resident caches + host mirrors of device carries

    # -- mesh placement -----------------------------------------------------

    def _ctx(self):
        """Engine (mesh, rules) dispatch context — no-op without a mesh."""
        return self.engine._ctx()

    def _put_b(self, x):
        """Slot-axis carry -> mesh (identity without one)."""
        return self.engine.put_batch(x)

    def _cache_init(self, batch: int, rows: int, pages: Optional[int]):
        """The traceable build of a zeroed unstacked cache tree."""
        def new_cache():
            return unstack_group_caches(init_cache(
                self.cfg, batch, rows, self.engine.decode_flags,
                dtype=self.engine.cache_dtype, pages=pages))
        return new_cache

    def _new_cache(self, batch: int, rows: int,
                   pages: Optional[int] = None):
        """A zeroed unstacked per-layer cache tree of ``batch`` x ``rows``
        (a paged pool of ``pages`` pages when given), built by ONE
        compiled program per geometry with no array inputs: one dispatch,
        and XLA folds ``init_cache``'s layer broadcast and the per-layer
        slices into one zero fill per output buffer (built eagerly, the
        tree costs several dispatches per leaf and layer, and a stacked
        transient).  On a mesh every leaf lands with the sharding
        ``shard_put_tree`` resolves for it."""
        key = (batch, rows, pages)
        fn = self._builders.get(key)
        if fn is None:
            build = self._cache_init(batch, rows, pages)
            kw = {}
            if self.mesh is not None:
                shape = jax.eval_shape(build)
                specs = tree_specs(shape,
                                   unstacked_cache_specs(self.cfg, shape),
                                   rules=self.engine.shard_rules,
                                   mesh=self.mesh)
                kw["out_shardings"] = jax.tree.map(
                    lambda _, s: jax.sharding.NamedSharding(self.mesh, s),
                    shape, specs)
            fn = self._builders[key] = jax.jit(build, **kw)
        return fn()

    # -- queue / admission --------------------------------------------------

    def _eff_mode(self, req: Request) -> str:
        return (req.dsa_mode if req.dsa_mode is not None
                else self.engine.decode_flags.dsa_mode)

    def _flags(self, mode: str):
        """Decode-segment / chunk-step flags for a dsa_mode (static —
        hashable RunFlags, one compiled instance per mode in use)."""
        return self.engine.run_flags("decode", mode)

    # -- paged-pool helpers ---------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        return -(-(len(req.prompt) + req.n_new) // self._page_rows)

    def _prefix_ctx(self, req: Request, bucket: int, mode: str,
                    chunked: bool):
        """(prefix registry key, whole shared pages) for a request's
        declared prefix under this group's geometry — (None, 0) when the
        request has none, the prefix spans no whole page, or the group
        runs the blocking path (seeding needs the staging cache)."""
        if not (self.paged and chunked and req.prefix_key
                and req.prefix_len):
            return None, 0
        n_sh = req.prefix_len // self._page_rows
        if n_sh == 0:
            return None, 0
        return (req.prefix_key, req.prefix_len, bucket, mode), n_sh

    def _zero_dirty(self, pages: Sequence[int]) -> None:
        """Zero the dirty subset of freshly mapped ``pages`` on device
        (pow2-bucketed 0-padded id widths, so zeroing adds a handful of
        compiles total, not one per allocation size)."""
        d = self.pool.take_dirty(pages)
        if not d:
            return
        ids = np.zeros((pow2_bucket(len(d), 4),), np.int32)
        ids[:len(d)] = d
        with self._ctx():
            self._caches = self._zero_pages(self._caches, jnp.asarray(ids))

    def submit(self, req: Request) -> None:
        plen = int(np.asarray(req.prompt).shape[-1])
        if plen == 0:
            raise ValueError(f"request {req.rid}: empty prompt — decode "
                             f"needs at least one context token")
        if req.rid in self._live:
            # a silent duplicate would overwrite the first request's slot
            # bookkeeping and drop one of the two results on the floor
            raise ValueError(f"request {req.rid}: rid already in flight — "
                             f"rids must be unique until their result is "
                             f"emitted")
        if plen + req.n_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {plen} + n_new {req.n_new} "
                f"exceeds max_len {self.max_len}")
        if req.prefix_len:
            if not (0 < req.prefix_len <= plen):
                raise ValueError(
                    f"request {req.rid}: prefix_len {req.prefix_len} "
                    f"outside (0, prompt_len {plen}]")
            if req.prefix_key is None:
                # hash the declared prefix tokens so equal prefixes match
                # without callers coordinating keys
                req.prefix_key = hashlib.sha1(np.ascontiguousarray(
                    np.asarray(req.prompt, np.int32)[:req.prefix_len]
                ).tobytes()).hexdigest()
        if self.paged:
            need = -(-(plen + req.n_new) // self._page_rows)
            if need > self.pool_pages - 1:
                raise ValueError(
                    f"request {req.rid}: needs {need} cache pages but the "
                    f"pool holds {self.pool_pages - 1} allocatable pages — "
                    f"raise pool_pages or shorten the request")
        if req.temperature <= 0.0:
            raise ValueError(f"request {req.rid}: temperature must be > 0")
        if req.dsa_mode is not None:
            allowed = (set(DSA_MODES)
                       if self.engine.decode_flags.long_context
                       else {self.engine.decode_flags.dsa_mode})
            if req.dsa_mode not in allowed:
                raise ValueError(
                    f"request {req.rid}: dsa_mode {req.dsa_mode!r} needs a "
                    f"cache layout this engine doesn't hold ({allowed})")
        if (self.queue_cap is not None
                and len(self.queue) >= self.queue_cap):
            victim = self._shed_victim(req)
            if victim is not None:
                # shed results never touched a slot: empty tokens, admit ==
                # finish == arrival (deterministic — no wall clock involved)
                self._emit(None, victim, np.zeros((0,), np.int32),
                           victim.arrival_s, victim.arrival_s, "shed")
                if victim is req:
                    return
        self._live.add(req.rid)
        self._enq_s[req.rid] = time.monotonic()
        self.queue.append(req)
        if self.telemetry is not None:
            self.telemetry.on_submit(req.rid, len(self.queue))

    def free_slots(self) -> List[int]:
        return [i for i in range(self.slots)
                if self._slot[i] is None and i not in self._reserved]

    def has_work(self) -> bool:
        return (bool(self.queue) or self._pf is not None
                or any(s is not None for s in self._slot))

    def _next_admissible(self) -> Optional[int]:
        """Queue index of the first request admissible under the current
        segment mode (any request when the engine is idle) — segments are
        mode-affine, so other-mode requests wait for an idle drain.

        Aging (``max_mode_wait_s``): an other-mode request that has been
        queued longer than the wait budget FORCES a drain — admission of
        same-mode traffic stops (returns None) so the engine empties and
        switches modes; at the idle switch FIFO puts the starved request
        (older than everything admitted since) first.  Without aging,
        sustained same-mode traffic could starve an other-mode request
        indefinitely (the ROADMAP's mode-affine starvation item); with it
        the wait is bounded by the budget plus one drain."""
        if not self.queue:
            return None
        if self._pf is None and not any(s is not None for s in self._slot):
            self._cur_mode = None         # idle: free to switch dsa_mode
        if self._cur_mode is None:
            return 0
        if self.max_mode_wait_s is not None:
            now = time.monotonic()
            if any(self._eff_mode(r) != self._cur_mode
                   and now - self._enq_s.get(r.rid, now)
                   >= self.max_mode_wait_s for r in self.queue):
                return None               # aged other-mode request: drain
        for i, r in enumerate(self.queue):
            if self._eff_mode(r) == self._cur_mode:
                return i
        return None

    def _group_for_admission(self, k: int, anchor: int) -> List[Request]:
        """Pop up to ``k`` queued requests sharing the anchor request's
        (prompt bucket, dsa_mode) for one shared prefill batch.
        Same-bucket only: a row's prefill program (and hence its tokens,
        bitwise) must match what a solo ``Engine.generate`` at that prompt
        bucket would run.  Skipped requests keep their relative order.

        Paged engines also group by declared (prefix_key, prefix_len) —
        sharers co-admit so the shared pages are charged once — and cap
        the group at what the page pool can fund NOW (shared prefix pages
        cost nothing on a registry hit; a MISS's first slotted member
        funds them).  An unfundable anchor LRU-evicts idle prefix
        registrations, and failing that the whole queue waits for slot
        retirements to return pages (returns an empty group)."""
        rest: deque = deque()
        for _ in range(anchor):
            rest.append(self.queue.popleft())
        first = self.queue.popleft()
        b0 = self.engine.prompt_bucket(len(first.prompt))
        m0 = self._eff_mode(first)
        budget = None
        if self.paged:
            use_chunked = self.chunked and can_chunk_prefill(
                self.cfg, m0, moe_dense=self.engine.moe_dense)
            key0, n_sh = self._prefix_ctx(first, b0, m0, use_chunked)
            hit = (key0 is not None
                   and self.pool.lookup_prefix(key0) is not None)
            shared_pending = 0 if hit else n_sh

            def cost(r):
                if r.n_new <= 1:
                    return 0          # never slotted: staging only
                return self._pages_needed(r) - n_sh + shared_pending

            if self.injector is not None:
                self.injector.telemetry = self.telemetry
            forced = (self.injector is not None
                      and self.injector.take("pool_exhaust") is not None)
            need0 = cost(first)
            if not forced and need0 > self.pool.available():
                self.pool.evict_for(need0, keep=key0)
            if forced or need0 > self.pool.available():
                # unfundable anchor: bounded retry instead of the old
                # unconditional requeue (a livelock when nothing in flight
                # could ever return pages).  With resident or chunking work
                # the anchor waits for retirements as before; with the
                # engine otherwise idle it sheds after admit_retries
                # attempts — nothing will ever free the pages it needs.
                n = self._unfundable.get(first.rid, 0) + 1
                self._unfundable[first.rid] = n
                if (n > self.admit_retries and self._pf is None
                        and not any(s is not None for s in self._slot)):
                    self._emit(None, first, np.zeros((0,), np.int32),
                               first.arrival_s, first.arrival_s, "shed")
                else:
                    rest.append(first)
                while rest:
                    self.queue.appendleft(rest.pop())
                return []
            self._unfundable.pop(first.rid, None)
            budget = self.pool.available() - need0
            if first.n_new > 1:
                shared_pending = 0
        group = [first]
        while self.queue and len(group) < k:
            r = self.queue.popleft()
            if (self.engine.prompt_bucket(len(r.prompt)) == b0
                    and self._eff_mode(r) == m0
                    and (r.prefix_key, r.prefix_len)
                    == (first.prefix_key, first.prefix_len)):
                if budget is not None:
                    c = cost(r)
                    if c > budget:
                        rest.append(r)
                        continue
                    budget -= c
                    if r.n_new > 1:
                        shared_pending = 0
                group.append(r)
            else:
                rest.append(r)
        while rest:
            self.queue.appendleft(rest.pop())
        for r in group:               # admitted: drop their aging stamps
            self._enq_s.pop(r.rid, None)
        return group

    def _sample_tok0(self, last_row, req: Request):
        """Sample a request's first token from its prefill logits row with
        its own PRNG chain (replays Engine.generate's chain bitwise)."""
        key = jax.random.PRNGKey(req.seed)
        tok0, key = _sample(last_row, key, req.greedy,
                            jnp.asarray(req.temperature, jnp.float32))
        return int(np.asarray(tok0)[0, 0]), np.asarray(key)

    def _activate(self, slot: int, req: Request, tok0: int, key,
                  admit_s: float, first_s: float) -> None:
        self._tok[slot, 0] = tok0
        self._keys[slot] = key
        self._active[slot] = True
        self._greedy[slot] = req.greedy
        self._temps[slot] = req.temperature
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        # preallocated at the full generation size: prompt + tok0 +
        # (n_new - 1) decoded tokens; segments append in place
        hist = np.empty((prompt.size + req.n_new,), np.int32)
        hist[:prompt.size] = prompt
        hist[prompt.size] = tok0
        self._slot[slot] = _SlotState(req, tok0, [], req.n_new - 1, admit_s,
                                      first_token_s=first_s, history=hist,
                                      hist_len=prompt.size + 1)
        if self.telemetry is not None:
            self.telemetry.on_first_token(req.rid)

    def _admit_group(self, slots: List[int], group: List[Request], mode,
                     clock, results: List[RequestResult]) -> None:
        """LEGACY blocking admission: prefill a same-bucket group in ONE
        padded whole-prompt batch and insert each row into a freed slot.
        Two fixed prefill batch shapes per bucket (1 row for singleton
        groups, ``slots`` rows otherwise — surplus rows repeat a real
        prompt and are discarded), so admission never recompiles per
        group; ``warmup`` precompiles both.  Every resident decoder stalls
        for the whole prompt — the cost the chunked path removes."""
        admit_s = clock()                 # the group's admission starts
        with span(self.telemetry, "serve.admit.blocking") as sp:
            bpf = 1 if len(group) == 1 else self.slots
            bucket = self.engine.prompt_bucket(len(group[0].prompt))
            mat = np.full((bpf, bucket), self.engine.pad_id, np.int32)
            lengths = np.empty((bpf,), np.int32)
            for j in range(bpf):
                r = group[min(j, len(group) - 1)]
                p = np.asarray(r.prompt, np.int32)
                mat[j, :len(p)] = p
                lengths[j] = len(p)
            last, pcaches, tp = self.engine.prefill(mat, cache_len=bucket,
                                                    lengths=lengths,
                                                    dsa_mode=mode)
            if self.telemetry is not None:
                self.telemetry.on_admission(sp, len(group), bucket, mode,
                                            kind="blocking")
            self.stats["prefill_s"] += tp
            if any(s is not None for s in self._slot):
                self.stats["stall_s"] += tp   # resident decoders sat idle
            self.stats["admitted"] += len(group)
            now = clock()                 # prefill has completed (blocking)
            pcaches = unstack_group_caches(pcaches)
            free = iter(slots)
            for j, req in enumerate(group):
                tok0, key = self._sample_tok0(last[j:j + 1, -1], req)
                self.stats["useful_tokens"] += 1  # the prefill-sampled tok0
                if req.n_new == 1:   # first token IS the whole generation
                    if self.telemetry is not None:
                        self.telemetry.on_first_token(req.rid)
                    self._emit(results, req, np.asarray([tok0], np.int32),
                               admit_s, now, "ok", first_s=now)
                    continue
                slot = next(free)
                if self.paged:
                    # blocking + paged (archs that page but can't chunk):
                    # all-private allocation, no prefix sharing
                    npt = self._pages_needed(req)
                    pages = self.pool.alloc(npt)
                    self._zero_dirty(pages)
                    self.pool.assign_slot(slot, pages, 0)
                    row = np.zeros((self._n_kb,), np.int32)
                    row[:npt] = pages
                    with self._ctx():
                        self._caches = self._insert_paged(
                            self._caches, pcaches,
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(j, jnp.int32), jnp.asarray(row))
                else:
                    with self._ctx():
                        self._caches = self._insert(
                            self._caches, pcaches,
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(j, jnp.int32))
                self._activate(slot, req, tok0, key, admit_s, now)

    # -- chunked admission (default) ----------------------------------------

    def _start_chunked_group(self, free: List[int], group: List[Request],
                             mode: str, clock) -> None:
        """Begin streaming a same-bucket group through a fresh bucket-sized
        staging cache; resident slots are reserved now, filled at group
        completion.  Two staging widths per bucket (1 / ``slots``), like
        the legacy path, so the chunk program set stays fixed."""
        admit_s = clock()                 # the group's admission starts
        bucket = self.engine.prompt_bucket(len(group[0].prompt))
        c = min(self.chunk_tokens, pow2_bucket(bucket, self._chunk_floor))
        bpf = 1 if len(group) == 1 else self.slots
        n_chunks = max(1, -(-max(len(r.prompt) for r in group) // c))
        mat = np.full((bpf, n_chunks * c), self.engine.pad_id, np.int32)
        lengths = np.empty((bpf,), np.int32)
        for j in range(bpf):
            r = group[min(j, len(group) - 1)]
            p = np.asarray(r.prompt, np.int32)
            mat[j, :len(p)] = p
            lengths[j] = len(p)
        slots = []
        it = iter(free)
        for r in group:
            slot = next(it) if r.n_new > 1 else None
            if slot is not None:
                self._reserved.add(slot)
            slots.append(slot)
        tbls = None
        skip = 0
        shared = None
        if self.paged:
            key, n_sh = self._prefix_ctx(group[0], bucket, mode, True)
            shared = self.pool.lookup_prefix(key) if key else None
            hit = shared is not None
            if not hit and key is not None and any(
                    s is not None for s in slots):
                # prefix MISS with a slotted writer: allocate + register
                # the shared pages now; the members' inserts fill them
                # (each rewrites identical bytes — same prefix, same
                # staging geometry), and single-flight admission (_pf)
                # means they're filled before any HIT group can start
                shared = self.pool.alloc(n_sh)
                self._zero_dirty(shared)
                self.pool.register_prefix(key, shared)
            tbls = []
            for r, slot in zip(group, slots):
                if slot is None:
                    tbls.append(None)     # staging-only member: no pages
                    continue
                npt = self._pages_needed(r)
                row = np.zeros((self._n_kb,), np.int32)
                if shared is not None:
                    self.pool.retain(shared)
                    priv = self.pool.alloc(npt - n_sh)
                    self._zero_dirty(priv)
                    pages = list(shared) + priv
                    self.pool.assign_slot(slot, pages, n_sh)
                else:
                    pages = self.pool.alloc(npt)
                    self._zero_dirty(pages)
                    self.pool.assign_slot(slot, pages, 0)
                row[:len(pages)] = pages
                tbls.append(row)
            if hit:
                # prefix HIT: seed the staging cache from the shared pages
                # and skip the whole-page prefix chunks outright (near-zero
                # TTFT for the shared part).  Every member still runs its
                # FINISHING chunk — its first token samples there — hence
                # the min-cap; the chunks that do run replay the dense
                # chunk programs bitwise because the seeded rows are the
                # bytes chunking [0, skip*c) would have written.
                skip = min(n_sh * self._page_rows // c,
                           min(-(-len(r.prompt) // c) for r in group) - 1)
                self.stats["prefix_hits"] += len(group)
                self.stats["prefix_tokens_reused"] += skip * c * len(group)
        with span(self.telemetry, "serve.admit.staging") as sp:
            caches = self._staging(bpf, bucket)
            self.stats["staging_builds"] += 1
            if skip > 0:
                rpages = jnp.asarray(
                    shared[:skip * c // self._page_rows], jnp.int32)
                with self._ctx():
                    caches = self._seed(caches, self._caches, rpages,
                                        skip * c)
            if self.telemetry is not None:
                self.telemetry.on_admission(sp, len(group), bucket, mode,
                                            kind="chunked",
                                            prefix_skip_chunks=skip)
        self._pf = _PrefillGroup(group, slots, bucket, c, mode, caches,
                                 lengths, j=skip, n_chunks=n_chunks, mat=mat,
                                 tbls=tbls, admit_s=admit_s)
        self.stats["admitted"] += len(group)

    def _chunk_burst(self) -> int:
        """How many chunks to run before yielding to a decode segment.
        With no resident decoder there is no one to yield to — drain the
        whole group.  Otherwise bound the decoder stall at roughly ONE
        segment's worth of chunk compute, self-tuned from the running
        chunk/segment timings (a segment is a fused seg_len-step scan, so
        one chunk per segment would stretch ingestion by the
        segment/chunk cost ratio while the reserved slots idle)."""
        pf = self._pf
        remaining = pf.n_chunks - pf.j
        if not any(s is not None for s in self._slot):
            return remaining
        st = self.stats
        if st["chunks"] and st["segments"] and st["chunk_s"] > 0:
            per_chunk = st["chunk_s"] / st["chunks"]
            per_seg = st["segment_s"] / st["segments"]
            return int(np.clip(round(per_seg / max(per_chunk, 1e-9)),
                               1, remaining))
        return 1                  # cold start: no timings yet

    def step_prefill(self, clock, results: List[RequestResult]) -> None:
        """Run a stall-bounded BURST of chunks of the in-flight admission
        group (no-op without one).  The serving loop alternates this with
        decode segments, so resident decoders keep producing tokens while
        a long prompt is ingested.  A member whose prompt completes
        mid-group is inserted and activated IMMEDIATELY — it decodes in
        the very next segment while its co-admitted longer prompts are
        still chunking.  Chunk dispatches only sync the host on a
        member's final chunk (sampling its first token); intermediate
        chunks pipeline asynchronously."""
        pf = self._pf
        if pf is None:
            return
        with span(self.telemetry, "serve.chunk_burst") as sp:
            self._run_chunks(pf, clock, results, sp)
        if pf.j >= pf.n_chunks:
            self._pf = None               # all members inserted already

    def _run_chunks(self, pf: _PrefillGroup, clock,
                    results: List[RequestResult], sp: span) -> None:
        """The chunk burst of ``step_prefill`` (inside its span)."""
        bpf = pf.lengths.shape[0]
        active = self._put_b(np.ones((bpf,), bool))
        flags = self._flags(pf.mode)
        stalled = any(st is not None for st in self._slot)
        t0 = time.monotonic()
        synced = False
        burst = self._chunk_burst()
        for _ in range(burst):
            j = pf.j
            toks = pf.mat[:, j * pf.chunk:(j + 1) * pf.chunk]
            chunk_len = np.clip(pf.lengths - j * pf.chunk, 0,
                                pf.chunk).astype(np.int32)
            with self._ctx():
                last, pf.caches = self._chunk(
                    self.engine.params, pf.caches, self._put_b(toks),
                    self._put_b(chunk_len), active, flags=flags,
                    sel_len=pf.bucket)
            pf.j += 1
            finishing = [i for i, r in enumerate(pf.reqs)
                         if -(-len(r.prompt) // pf.chunk) == j + 1
                         and i not in pf.dead]
            if not finishing:
                continue
            last = np.asarray(last)       # sync: this chunk has completed
            synced = True
            now = clock()
            for i in finishing:
                req = pf.reqs[i]
                tok0, key = self._sample_tok0(last[i:i + 1], req)
                self.stats["useful_tokens"] += 1
                if req.n_new == 1:        # retires without touching a slot
                    if self.telemetry is not None:
                        self.telemetry.on_first_token(req.rid)
                    self._emit(results, req, np.asarray([tok0], np.int32),
                               pf.admit_s, now, "ok", first_s=now)
                    continue
                slot = pf.slots[i]        # early activation: decode NOW
                with span(self.telemetry, "serve.insert"):
                    with self._ctx():
                        if self.paged:
                            self._caches = self._insert_paged(
                                self._caches, pf.caches,
                                jnp.asarray(slot, jnp.int32),
                                jnp.asarray(i, jnp.int32),
                                jnp.asarray(pf.tbls[i]))
                        else:
                            self._caches = self._insert(
                                self._caches, pf.caches,
                                jnp.asarray(slot, jnp.int32),
                                jnp.asarray(i, jnp.int32))
                    self._reserved.discard(slot)
                    self._activate(slot, req, tok0, key, pf.admit_s, now)
        if not synced:
            jax.block_until_ready(jax.tree.leaves(pf.caches)[0])
        dt = time.monotonic() - t0
        self.stats["chunks"] += burst
        self.stats["chunk_s"] += dt
        if stalled:
            self.stats["stall_s"] += dt
        if self.telemetry is not None:
            self.telemetry.on_chunk_burst(sp, dt, burst, pf.bucket, pf.mode,
                                          len(pf.reqs))

    def admit_ready(self, clock, results: List[RequestResult]) -> None:
        """``clock``: zero-arg callable giving seconds since serve start;
        admission/finish timestamps are sampled AFTER blocking work.
        Chunked mode only STARTS a group here (one in flight at a time) —
        its chunks run via ``step_prefill`` between decode segments."""
        with span(self.telemetry, "serve.admit"):
            self._admit(clock, results)

    def _admit(self, clock, results: List[RequestResult]) -> None:
        """The body of ``admit_ready`` (inside its span)."""
        if self._pending:
            # results emitted outside a results-carrying call (submit-time
            # sheds, cancel(), unfundable sheds) surface at the next
            # admission point
            results.extend(self._pending)
            self._pending.clear()
        self._reap(clock, results)
        while self.queue:
            if self._pf is not None:
                break                     # chunked group already in flight
            free = self.free_slots()
            if not free:
                break
            anchor = self._next_admissible()
            if anchor is None:
                break                     # other-mode requests wait: drain
            group = self._group_for_admission(len(free), anchor)
            if not group:
                break                     # page pool can't fund the anchor
            mode = self._eff_mode(group[0])
            self._cur_mode = mode
            # a per-request dsa_mode override can leave the chunk-exactness
            # envelope (DSA-over-MLA): such groups fall back to blocking
            if self.chunked and can_chunk_prefill(
                    self.cfg, mode, moe_dense=self.engine.moe_dense):
                self._start_chunked_group(free, group, mode, clock)
                break
            self._admit_group(free, group, mode, clock, results)

    # -- request lifecycle (deadlines / cancellation / shedding) ------------

    def _eff_deadline(self, req: Request) -> Optional[float]:
        return (req.deadline_s if req.deadline_s is not None
                else self.deadline_s)

    def _emit(self, results: Optional[List[RequestResult]], req: Request,
              tokens, admit_s: float, finish_s: float, status: str,
              first_s: float = 0.0) -> None:
        """Retire ``req`` with a typed result: drops its queue bookkeeping
        (rid becomes reusable), counts non-ok statuses, and appends to
        ``results`` — or to ``self._pending`` (flushed at the next
        admission point) when the caller carries no results list."""
        self._live.discard(req.rid)
        self._enq_s.pop(req.rid, None)
        self._unfundable.pop(req.rid, None)
        if status != "ok":
            self.stats[status] += 1
        res = RequestResult(
            req.rid, np.asarray(tokens, np.int32).reshape(-1),
            int(np.asarray(req.prompt).shape[-1]), req.n_new,
            req.arrival_s, admit_s, finish_s, first_token_s=first_s,
            status=status, deadline_s=self._eff_deadline(req))
        if self.telemetry is not None:
            # the single retirement path: every result feeds the metrics
            # registry exactly once, so the Prometheus per-status counters
            # can never disagree with summarize() over the same results
            self.telemetry.on_retire(res)
        (results if results is not None else self._pending).append(res)

    def _partial(self, st: _SlotState) -> np.ndarray:
        """A retiring resident slot's tokens so far: tok0 + every
        collected segment chunk."""
        return np.concatenate(
            [np.asarray([st.tok0], np.int32)] + st.collected)

    def _retire_slot(self, i: int) -> None:
        """Free slot ``i`` outside the normal end-of-generation path:
        the host ``active`` mirror is the next segment's dispatch truth,
        so clearing it freezes the slot (kv_len = 0, writes dropped) and
        co-resident slots never see a perturbation; pages return exactly
        like a normal retirement."""
        self._slot[i] = None
        self._active[i] = False
        if self.paged:
            self.pool.free_slot(i)

    def _kill_pf_member(self, pf: _PrefillGroup, i: int) -> None:
        """Remove member ``i`` from an in-flight chunked admission: its
        reserved slot and pages free now, its row keeps chunking (group
        geometry is fixed) but never activates; the group's chunk count
        shrinks to the surviving members' longest prompt."""
        slot = pf.slots[i]
        if slot is not None:
            self._reserved.discard(slot)
            if self.paged and slot in self.pool.slot_pages:
                self.pool.free_slot(slot)
            pf.slots[i] = None
        pf.dead.add(i)
        alive = [j for j in range(len(pf.reqs)) if j not in pf.dead]
        if not alive:
            self._pf = None
        else:
            pf.n_chunks = max(-(-len(pf.reqs[j].prompt) // pf.chunk)
                              for j in alive)

    def _shed_victim(self, req: Request) -> Optional[Request]:
        """Overload: whom to shed when the admission queue sits at
        ``queue_cap``.  "reject" sheds the arrival, "oldest" the longest-
        queued request, "lowest-priority" the lowest-priority queued
        request unless the arrival is lower still (ties reject the
        arrival — stable under an equal-priority flood).  The returned
        victim is already off the queue."""
        if self.shed_policy == "reject":
            return req
        if self.shed_policy == "oldest":
            return self.queue.popleft()
        victim = min(self.queue, key=lambda r: r.priority)
        if req.priority <= victim.priority:
            return req
        self.queue = deque(r for r in self.queue if r is not victim)
        return victim

    def _reap(self, clock, results: List[RequestResult]) -> None:
        """Retire deadline-expired work at a segment boundary: queued
        requests time out before admission (empty tokens), chunking
        members leave their group, resident slots freeze via the active
        mask and surface their partial tokens.  Runs at every admission
        point, so expiry always lands BETWEEN segments."""
        if self.deadline_s is None and not self._any_deadlines:
            return
        now = clock()

        def expired(r):
            d = self._eff_deadline(r)
            return d is not None and now - r.arrival_s > d

        if any(expired(r) for r in self.queue):
            keep: deque = deque()
            for r in self.queue:
                if expired(r):
                    self._emit(results, r, np.zeros((0,), np.int32),
                               now, now, "timeout")
                else:
                    keep.append(r)
            self.queue = keep
        pf = self._pf
        if pf is not None:
            for i, r in enumerate(pf.reqs):
                if i not in pf.dead and expired(r):
                    self._emit(results, r, np.zeros((0,), np.int32),
                               pf.admit_s, now, "timeout")
                    self._kill_pf_member(pf, i)
        for i, st in enumerate(self._slot):
            if st is not None and expired(st.req):
                self._emit(results, st.req, self._partial(st), st.admit_s,
                           now, "timeout", first_s=st.first_token_s)
                self._retire_slot(i)

    def cancel(self, rid: int, now: float = 0.0) -> bool:
        """Cancel a request wherever it lives — queued (empty tokens),
        mid-chunked-admission, or resident (partial tokens, slot and
        pages freed exactly like a normal retirement; co-resident slots
        untouched).  Returns False for unknown or already-finished rids.
        The result surfaces at the next admission point with status
        "cancelled"."""
        for r in self.queue:
            if r.rid == rid:
                self.queue = deque(x for x in self.queue if x is not r)
                self._emit(None, r, np.zeros((0,), np.int32), now, now,
                           "cancelled")
                return True
        pf = self._pf
        if pf is not None:
            for i, r in enumerate(pf.reqs):
                if r.rid == rid and i not in pf.dead:
                    self._emit(None, r, np.zeros((0,), np.int32), now, now,
                               "cancelled")
                    self._kill_pf_member(pf, i)
                    return True
        for i, st in enumerate(self._slot):
            if st is not None and st.req.rid == rid:
                self._emit(None, st.req, self._partial(st), st.admit_s,
                           now, "cancelled", first_s=st.first_token_s)
                self._retire_slot(i)
                return True
        return False

    @property
    def _any_deadlines(self) -> bool:
        return (any(r.deadline_s is not None for r in self.queue)
                or (self._pf is not None
                    and any(r.deadline_s is not None
                            for r in self._pf.reqs))
                or any(s is not None and s.req.deadline_s is not None
                       for s in self._slot))

    def _scrub_all(self, clock, results: List[RequestResult]) -> None:
        """A device-side segment failure invalidated the DONATED resident
        caches mid-dispatch: fail every in-flight request (resident slots
        keep their pre-segment partial tokens, chunking members surface
        empty), rebuild the resident cache and page pool from scratch
        (registered prefix pages live in the cache, so the registry dies
        with it), and keep serving the queue."""
        now = clock()
        for i, st in enumerate(self._slot):
            if st is None:
                continue
            self._emit(results, st.req, self._partial(st), st.admit_s,
                       now, "failed", first_s=st.first_token_s)
            self._slot[i] = None
        pf = self._pf
        if pf is not None:
            for i, r in enumerate(pf.reqs):
                if i not in pf.dead:
                    self._emit(results, r, np.zeros((0,), np.int32),
                               pf.admit_s, now, "failed")
            self._pf = None
        self._reserved.clear()
        self._init_resident()

    def health(self) -> Dict[str, object]:
        """Liveness / degradation snapshot for a serving front door:
        occupancy, watchdog timings, failure counters, and the last
        recorded error."""
        pf = self._pf
        return {
            "resident": sum(s is not None for s in self._slot),
            "queued": len(self.queue),
            "reserved": len(self._reserved),
            "chunking": 0 if pf is None else len(pf.reqs) - len(pf.dead),
            "pool_free": self.pool.available() if self.paged else None,
            "segments": self.stats["segments"],
            "median_segment_s": self._watchdog.median_step_s,
            "slow_segments": len(self._watchdog.slow_steps),
            "watchdog_slow": self.stats["watchdog_slow"],
            "dispatch_failures": self.stats["dispatch_failures"],
            "proposer_failures": self.stats["proposer_failures"],
            "spec_degraded": self._spec_degraded,
            "failed": self.stats["failed"],
            "shed": self.stats["shed"],
            "cancelled": self.stats["cancelled"],
            "timeout": self.stats["timeout"],
            "last_error": self._last_error,
        }

    # -- warmup / reset ------------------------------------------------------

    def _init_resident(self) -> None:
        """(Re)build the resident cache, page pool, and every per-slot
        host mirror — shared by ``reset`` and the scrub-all recovery path
        (a rebuilt cache zeroes registered prefix pages, so the pool and
        its prefix registry are rebuilt with it)."""
        self.pool = (PagePool(self.pool_pages, self._page_rows)
                     if self.paged else None)
        pages = self.pool_pages if self.paged else None
        shape = jax.eval_shape(self._cache_init(self.slots, self.max_len,
                                                pages))

        def record(path, log):
            name = _leaf_name(path)
            if name is not None:
                self._cache_logical[name] = tuple(log)

        jax.tree_util.tree_map_with_path(
            record, unstacked_cache_specs(self.cfg, shape),
            is_leaf=is_spec_leaf)
        self._caches = self._new_cache(self.slots, self.max_len, pages)
        self._tok = np.zeros((self.slots, 1), np.int32)
        self._keys = np.zeros((self.slots, 2), np.uint32)
        self._active = np.zeros((self.slots,), bool)
        self._greedy = np.ones((self.slots,), bool)
        self._temps = np.ones((self.slots,), np.float32)
        self._slot = [None] * self.slots
        self._reserved: Set[int] = set()
        self._pf: Optional[_PrefillGroup] = None
        self._cur_mode: Optional[str] = None

    def weight_bytes_per_device(self) -> int:
        """Per-device resident weight bytes of the inner engine — ~1/tp of
        the replicated footprint on a tensor-parallel serving mesh."""
        return self.engine.weight_bytes_per_device()

    def reset(self) -> None:
        """Zero all slots, the queue, and stats (compiled functions are
        kept)."""
        self.stats = {"segments": 0, "useful_tokens": 0, "admitted": 0,
                      "prefill_s": 0.0, "chunks": 0, "chunk_s": 0.0,
                      "stall_s": 0.0, "segment_s": 0.0,
                      "spec_rounds": 0, "spec_emitted": 0, "draft_s": 0.0,
                      "accept_hist": [0] * (self.spec + 1),
                      "prefix_hits": 0, "prefix_tokens_reused": 0,
                      "staging_builds": 0,
                      "shed": 0, "cancelled": 0, "timeout": 0, "failed": 0,
                      "dispatch_failures": 0, "proposer_failures": 0,
                      "watchdog_slow": 0}
        self._enq_s: Dict[int, float] = {}
        self._pending: List[RequestResult] = []
        self._live: Set[int] = set()
        self._unfundable: Dict[int, int] = {}
        self._spec_degraded = False
        self._spec_fail_streak = 0
        self._last_error: Optional[str] = None
        self._watchdog = StepWatchdog()
        self._init_resident()
        self.queue.clear()
        self._probe_prev.clear()
        if self.telemetry is not None:
            # the metrics registry, trace ring, and open spans restart
            # with the engine; the compile log survives (the compiled
            # programs do too), so health()-after-reset() and a fresh
            # Prometheus snapshot both read as zeroed
            self.telemetry.reset()

    def warmup(self, prompt_lens: Sequence[int]) -> None:
        """Precompile every admission/chunk/prefill/segment shape for the
        prompt buckets covering ``prompt_lens`` (at both admission widths,
        1 and ``slots``), then reset.  This is the fixed chunk-shape set of
        the recompilation contract; a serving loop that skips this
        compiles lazily on first use of each bucket.  Per-request dsa_mode
        overrides compile lazily on their first segment.

        A segment that fails to dispatch here (it does not compile, or the
        device runs out of memory) raises: serving would fail every request
        the same way, so warmup surfaces the cause instead of scrubbing."""
        buckets = sorted({self.engine.prompt_bucket(int(l))
                          for l in prompt_lens})
        sink: List[RequestResult] = []
        rid = -1
        self._warming = True
        try:
            for b in buckets:
                prompt = np.ones((min(b, self.max_len - 2),), np.int32)
                for n in (1, min(self.slots + 1, self.slots * 2)):
                    group = [Request(rid - j, prompt, 2) for j in range(n)]
                    for r in group:
                        self.submit(r)
                    while self.has_work():
                        self.admit_ready(lambda: 0.0, sink)
                        self.step_prefill(lambda: 0.0, sink)
                        if any(s is not None for s in self._slot):
                            self._step_decode(lambda: 0.0, sink)
                    rid -= n
        finally:
            self._warming = False
        self.reset()

    # -- serving-path probes ---------------------------------------------------

    def first_step_logits(self, prompts: Sequence[np.ndarray],
                          first_tokens: Optional[np.ndarray] = None,
                          bucket: Optional[int] = None
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Logits of the serving path for up to ``slots`` prompts, for
        checking one DSA path against another: the prompts stream through a
        staging cache of ``bucket`` rows (default: the longest prompt's
        bucket) with the chunk program chunked admission runs, then one
        decode step runs on that cache from ``first_tokens`` (default: the
        greedy first tokens; pass another path's so both steps see the same
        input).  Returns (prompt_logits, step_logits), each (len(prompts),
        vocab) float32.  The resident serving state is not touched."""
        n = len(prompts)
        assert 0 < n <= self.slots, (n, self.slots)
        longest = max(len(p) for p in prompts)
        bucket = bucket or self.engine.prompt_bucket(longest)
        # the decode step writes row len(prompt): it must be in the bucket
        assert longest < bucket, (longest, bucket)
        c = min(self.chunk_tokens, pow2_bucket(bucket, self._chunk_floor))
        bpf = 1 if n == 1 else self.slots
        mat = np.full((bpf, -(-longest // c) * c), self.engine.pad_id,
                      np.int32)
        lengths = np.empty((bpf,), np.int32)
        for j in range(bpf):
            p = np.asarray(prompts[min(j, n - 1)], np.int32)
            mat[j, :len(p)] = p
            lengths[j] = len(p)
        caches = self._staging(bpf, bucket)
        flags = self._flags(self.engine.decode_flags.dsa_mode)
        active = self._put_b(np.ones((bpf,), bool))
        prompt_logits = np.zeros((bpf, self.cfg.vocab), np.float32)
        with self._ctx():
            for j in range(mat.shape[1] // c):
                cl = np.clip(lengths - j * c, 0, c).astype(np.int32)
                last, caches = self._chunk(
                    self.engine.params, caches,
                    self._put_b(mat[:, j * c:(j + 1) * c]), self._put_b(cl),
                    active, flags=flags, sel_len=bucket)
                done = (lengths > j * c) & (lengths <= (j + 1) * c)
                prompt_logits[done] = np.asarray(last, np.float32)[done]
            tok = prompt_logits.argmax(-1).astype(np.int32)
            if first_tokens is not None:
                tok[:n] = first_tokens
            tok = tok[:, None]
            step, _ = self.engine._decode(self.engine.params,
                                          self._put_b(tok), caches,
                                          flags=flags)
        return prompt_logits[:n], np.asarray(step[:, -1], np.float32)[:n]

    def segment_hlo(self) -> str:
        """Compiled HLO text of the decode segment on the current resident
        state — shows which kernels serving runs."""
        with self._ctx():
            lowered = self._segment.lower(
                self.engine.params, self._put_b(self._tok), self._caches,
                self._put_b(self._keys), self._put_b(self._active),
                self._put_b(self._greedy), self._put_b(self._temps),
                self._put_b(np.zeros((self.slots,), np.int32)),
                self._put_b(np.zeros((self.slots,), bool)),
                flags=self._flags(self.engine.decode_flags.dsa_mode))
        return lowered.compile().as_text()

    def chunk_hlo(self, bucket: int, width: Optional[int] = None) -> str:
        """Compiled HLO text of the chunk program chunked admission runs
        for prompt bucket ``bucket`` at staging width ``width`` (1 or
        ``slots``, the default)."""
        bucket = self.engine.prompt_bucket(bucket)
        c = min(self.chunk_tokens, pow2_bucket(bucket, self._chunk_floor))
        bpf = width or self.slots
        caches = self._staging(bpf, bucket)
        with self._ctx():
            lowered = self._chunk.lower(
                self.engine.params, caches,
                self._put_b(np.zeros((bpf, c), np.int32)),
                self._put_b(np.zeros((bpf,), np.int32)),
                self._put_b(np.ones((bpf,), bool)),
                flags=self._flags(self.engine.decode_flags.dsa_mode),
                sel_len=bucket)
        return lowered.compile().as_text()

    # -- decode segments ----------------------------------------------------

    def run_segment(self, clock,
                    results: List[RequestResult]) -> None:
        remaining = np.asarray(
            [s.remaining if s else 0 for s in self._slot], np.int32)
        mode = self._cur_mode or self.engine.decode_flags.dsa_mode
        poison = np.zeros((self.slots,), bool)
        inj = self.injector
        if inj is not None:
            inj.telemetry = self.telemetry
            for i, st in enumerate(self._slot):
                if st is not None and inj.take("nan_logits",
                                               st.req.rid) is not None:
                    poison[i] = True
            if inj.take("dispatch") is not None:
                # transient dispatch failure: nothing launched, state is
                # untouched — the serving loop simply retries next round
                self.stats["dispatch_failures"] += 1
                return
        with span(self.telemetry, "serve.segment") as sp:
            ran = self._segment_body(clock, results, remaining, mode,
                                     poison, sp)
        if (ran and self._pf is None
                and not any(s is not None for s in self._slot)):
            self._cur_mode = None         # idle: free to switch dsa_mode

    def _segment_body(self, clock, results: List[RequestResult], remaining,
                      mode: str, poison, sp: span) -> bool:
        """Dispatch, wait for and emit one decode segment (inside the
        ``serve.segment`` span of ``run_segment``); False when the
        dispatch failed and the in-flight batch was scrubbed."""
        inj = self.injector
        tel = self.telemetry
        t0 = time.monotonic()
        self._watchdog.start()
        if inj is not None:
            f = inj.take("slow_segment")
            if f is not None:
                time.sleep(f.delay_s)   # stall INSIDE the watchdog window
        try:
            with span(tel, "serve.segment.dispatch"), self._ctx():
                tok, caches, keys, active, rem, fin, toks = self._segment(
                    self.engine.params, self._put_b(self._tok),
                    self._caches, self._put_b(self._keys),
                    self._put_b(self._active), self._put_b(self._greedy),
                    self._put_b(self._temps), self._put_b(remaining),
                    self._put_b(poison), flags=self._flags(mode))
            self._caches = caches
            with span(tel, "serve.segment.wait"):
                self._tok = np.array(tok)   # np.array: writable host copies
                self._keys = np.array(keys)
                self._active = np.array(active)
                fin = np.asarray(fin)
                toks = np.asarray(toks)               # (slots, seg_len)
        except Exception as e:              # noqa: BLE001 — fail partially
            if self._warming:
                raise
            # the dispatched computation itself failed: the DONATED caches
            # can no longer be trusted — fail the in-flight batch, rebuild,
            # keep serving the queue
            self._last_error = repr(e)
            self.stats["dispatch_failures"] += 1
            if tel is not None:
                tel.on_error(repr(e))
            self._scrub_all(clock, results)
            return False
        now = clock()                     # host copies above synced the step
        self.stats["segments"] += 1
        seg_wall = time.monotonic() - t0
        self.stats["segment_s"] += seg_wall
        slow = self._watchdog.stop(self.stats["segments"])
        if slow:
            self.stats["watchdog_slow"] += 1
        ut0 = self.stats["useful_tokens"]
        n_act = sum(s is not None for s in self._slot)
        with span(tel, "serve.segment.emit"):
            for i, st in enumerate(self._slot):
                if st is None:
                    continue
                if not fin[i]:
                    # non-finite logits row: this slot's sampled tokens are
                    # garbage from the first bad step on — fail ONLY this
                    # slot with its pre-segment tokens (co-resident rows
                    # never read another row's logits, so they are bitwise
                    # unaffected)
                    self._last_error = (f"request {st.req.rid}: non-finite "
                                        f"logits row in decode segment")
                    self._emit(results, st.req, self._partial(st),
                               st.admit_s, now, "failed",
                               first_s=st.first_token_s)
                    self._retire_slot(i)
                    continue
                emitted = min(st.remaining, self.seg_len)
                st.collected.append(toks[i, :emitted])
                st.extend_history(toks[i, :emitted])
                st.remaining -= emitted
                self.stats["useful_tokens"] += emitted
                if st.remaining == 0:
                    self._emit(results, st.req, self._partial(st),
                               st.admit_s, now, "ok",
                               first_s=st.first_token_s)
                    self._slot[i] = None      # slot freed; reset at admit
                    if self.paged:
                        self.pool.free_slot(i)  # non-shared pages return
        if tel is not None:
            tel.on_segment(
                sp, "decode_segment", seg_wall, mode=mode, active=n_act,
                tokens=self.stats["useful_tokens"] - ut0,
                queued=len(self.queue),
                resident=sum(s is not None for s in self._slot),
                pool_free=(self.pool.available() if self.paged else None),
                slow=slow)
            if (tel.sample_every
                    and self.stats["segments"] % tel.sample_every == 0):
                self._sparsity_probe(mode)
        return True

    # -- dynamic-sparsity sampling ------------------------------------------

    def _sparsity_probe(self, mode: str) -> None:
        """Sample the DSA block selection for the CURRENT resident state:
        replay one decode step with ``RunFlags.sel_probe`` set (a separate
        non-donating jit — the hot segment program is untouched) and read
        back ONLY the per-layer selection outputs; XLA dead-code
        eliminates the attention/MLP compute the probe does not return, so
        the probe costs roughly the selection path alone.  Records per-
        slot keep-rate, selected-block churn vs the previous sample of the
        same request, and cross-layer selection overlap."""
        tel = self.telemetry
        flags = self._flags(mode)
        if not (flags.long_context and flags.dsa_mode in ("block", "kernel")
                and self.cfg.mla is None):
            return                      # no materialized block selection
        if not self._active.any():
            return
        if self._probe is None:
            cfg = self.cfg

            def _probe_fn(params, tok, caches, active, flags):
                _, new = decode_step(params, cfg, flags, tok, caches,
                                     active=active)
                sel = {"sel_idx": [], "sel_ok": [], "sel_kv": []}
                for path, leaf in \
                        jax.tree_util.tree_flatten_with_path(new)[0]:
                    name = _leaf_name(path)
                    if name in sel:
                        sel[name].append(leaf)
                return sel

            self._probe = jax.jit(_probe_fn, static_argnames=("flags",))
            self._probe = tel.wrap_jit("probe", self._probe)
        pflags = dataclasses.replace(flags, sel_probe=True)
        with self._ctx():
            sel = self._probe(self.engine.params, self._put_b(self._tok),
                              self._caches, self._put_b(self._active),
                              flags=pflags)
        idxs = [np.asarray(x) for x in sel["sel_idx"]]
        oks = [np.asarray(x) for x in sel["sel_ok"]]
        kvs = np.asarray(sel["sel_kv"][0])
        bk = self.cfg.dsa.block_k
        samples = []
        for b in range(self.slots):
            st = self._slot[b]
            if st is None or not self._active[b]:
                continue
            n_valid = max(1, -(-int(kvs[b]) // bk))
            sets = [frozenset(idx[b][ok[b]].tolist())
                    for idx, ok in zip(idxs, oks)]
            keep = float(np.mean([min(1.0, len(s) / n_valid)
                                  for s in sets]))
            overlap = None
            if len(sets) > 1:
                js = [len(a & c) / max(len(a | c), 1)
                      for a, c in zip(sets, sets[1:])]
                overlap = float(np.mean(js))
            churn = None
            prev = self._probe_prev.get(b)
            if prev is not None and prev[0] == st.req.rid and sets[0]:
                u = len(sets[0] | prev[1])
                churn = 1.0 - len(sets[0] & prev[1]) / max(u, 1)
            self._probe_prev[b] = (st.req.rid, sets[0])
            samples.append((b, st.req.rid, keep, churn, overlap))
        tel.on_sparsity_sample(self.stats["segments"], samples)

    # -- speculative decode segments ----------------------------------------

    def run_spec_segment(self, clock, results: List[RequestResult]) -> None:
        """Speculative decode segment: ``spec_rounds`` draft-and-verify
        rounds over all resident slots.  Each round proposes K draft
        tokens per slot from its token history (host), verifies + commits
        them in ONE fused dispatch (repro.inference.speculative), and
        collects each slot's ragged accepted length — a slot emits 1 to
        K+1 tokens per round, bitwise the tokens its plain segments would
        emit.  Alternates with chunked admission exactly like plain
        segments; per-request dsa_mode overrides outside the speculation
        envelope fall back to plain segments (``_step_decode``)."""
        flags = dataclasses.replace(
            self._flags(self._cur_mode or self.engine.decode_flags.dsa_mode),
            spec_verify=True)
        with span(self.telemetry, "serve.segment") as sp:
            rounds_run = self._spec_rounds(clock, results, flags, sp)
        if not rounds_run and any(s is not None for s in self._slot):
            # the proposer crashed before any verify round: this segment
            # degrades to a plain fused segment so resident slots still
            # make progress (same tokens — spec == plain bitwise)
            self.run_segment(clock, results)
            return
        if self._pf is None and not any(s is not None for s in self._slot):
            self._cur_mode = None         # idle: free to switch dsa_mode

    def _spec_rounds(self, clock, results: List[RequestResult], flags,
                     sp: span) -> int:
        """The verify rounds of ``run_spec_segment`` (inside its
        ``serve.segment`` span); returns how many ran."""
        tel = self.telemetry
        t0 = time.monotonic()
        self._watchdog.start()
        draft_s0 = self.stats["draft_s"]
        ut0 = self.stats["useful_tokens"]
        rounds_run = 0
        for _ in range(self.spec_rounds):
            if not any(st is not None for st in self._slot):
                break
            # proposers read each slot's incremental history VIEW (read-
            # only) — O(new tokens) per round, not an O(T) re-concatenation
            # of prompt + every collected chunk (O(T^2) over a generation)
            ctxs = [_ro_view(st.history, st.hist_len) if st is not None
                    else np.zeros((1,), np.int32) for st in self._slot]
            td = time.monotonic()
            try:
                if (self.injector is not None
                        and self.injector.take("proposer") is not None):
                    raise FaultError("injected proposer fault")
                drafts = self.draft.propose(ctxs, self.spec)
                self._spec_fail_streak = 0
            except Exception as e:          # noqa: BLE001 — degrade, don't die
                # a crashing proposer only ever costs SPEED: spec segments
                # are bitwise plain decode, so this segment falls back to a
                # plain fused segment (run_spec_segment) and repeated
                # failures stop consulting the proposer entirely
                self.stats["draft_s"] += time.monotonic() - td
                self.stats["proposer_failures"] += 1
                self._last_error = repr(e)
                self._spec_fail_streak += 1
                if self._spec_fail_streak >= 3:
                    self._spec_degraded = True
                break
            self.stats["draft_s"] += time.monotonic() - td
            remaining = np.asarray(
                [st.remaining if st else 0 for st in self._slot], np.int32)
            with span(tel, "serve.segment.dispatch"), self._ctx():
                tok, caches, keys, nxt, emit, _, act2 = self._spec.verify(
                    self.engine.params, self._put_b(self._tok),
                    self._put_b(drafts), self._caches,
                    self._put_b(self._keys), self._put_b(self._active),
                    self._put_b(self._greedy), self._put_b(self._temps),
                    self._put_b(remaining), flags=flags)
            self._caches = caches
            with span(tel, "serve.segment.wait"):
                self._tok = np.array(tok)   # np.array: writable host copies
                self._keys = np.array(keys)
                self._active = np.array(act2)
                emit_np, nxt_np = np.asarray(emit), np.asarray(nxt)
            now = clock()                 # host copies above synced the round
            self.stats["spec_rounds"] += 1
            rounds_run += 1
            with span(tel, "serve.segment.emit"):
                self._emit_spec_round(results, emit_np, nxt_np, now)
        # stats feed the chunk-burst budget tuner (_chunk_burst): count a
        # segment only when rounds actually ran, and report DEVICE segment
        # time — host drafting excluded — so the tuner sizes admission
        # bursts against real verify cost, not draft-inflated wall time
        if rounds_run:
            self.stats["segments"] += 1
            seg_dev = ((time.monotonic() - t0)
                       - (self.stats["draft_s"] - draft_s0))
            self.stats["segment_s"] += seg_dev
            slow = self._watchdog.stop(self.stats["segments"])
            if slow:
                self.stats["watchdog_slow"] += 1
            if tel is not None:
                tel.on_segment(
                    sp, "spec_segment", seg_dev,
                    mode=flags.dsa_mode,
                    active=sum(s is not None for s in self._slot),
                    tokens=self.stats["useful_tokens"] - ut0,
                    queued=len(self.queue),
                    resident=sum(s is not None for s in self._slot),
                    slow=slow, rounds=rounds_run)
        return rounds_run

    def _emit_spec_round(self, results: List[RequestResult], emit_np,
                         nxt_np, now: float) -> None:
        """Collect each slot's accepted tokens of one verify round."""
        for i, st in enumerate(self._slot):
            if st is None:
                continue
            e = int(emit_np[i])
            if e == 0:
                continue
            st.collected.append(nxt_np[i, :e].astype(np.int32))
            st.extend_history(nxt_np[i, :e].astype(np.int32))
            st.remaining -= e
            self.stats["useful_tokens"] += e
            self.stats["spec_emitted"] += e
            self.stats["accept_hist"][e - 1] += 1
            if st.remaining == 0:
                self._emit(results, st.req, self._partial(st),
                           st.admit_s, now, "ok",
                           first_s=st.first_token_s)
                self._slot[i] = None  # slot freed; reset at admit
                if self.paged:
                    self.pool.free_slot(i)

    def _step_decode(self, clock, results: List[RequestResult]) -> None:
        """One decode segment at the current mode: speculative when the
        engine has spec on AND the segment's dsa_mode is inside the
        speculation envelope (``can_speculate`` — per-request overrides
        like DSA-over-MLA fall back), else a plain fused segment."""
        mode = self._cur_mode or self.engine.decode_flags.dsa_mode
        if (self.spec and not self._spec_degraded
                and can_speculate(self.cfg, mode, self.spec)):
            self.run_spec_segment(clock, results)
        else:
            self.run_segment(clock, results)

    # -- serving loops ------------------------------------------------------

    def run(self, requests: Sequence[Request]) -> Dict[int, np.ndarray]:
        """Deterministic drain (tests): queue everything, serve to empty,
        return {rid: tokens}.  One chunk of any in-flight admission runs
        between decode segments (the chunked-prefill interleave)."""
        for r in requests:
            self.submit(r)
        results: List[RequestResult] = []
        clock = lambda: 0.0
        while self.has_work():
            self.admit_ready(clock, results)
            self.step_prefill(clock, results)
            if any(s is not None for s in self._slot):
                self._step_decode(clock, results)
        results.extend(self._pending)     # e.g. everything shed pre-loop
        self._pending.clear()
        return {r.rid: r.tokens for r in results}

    def serve(self, workload: Sequence[Request]) -> List[RequestResult]:
        """Open-loop wall-clock serving: requests become visible at their
        ``arrival_s`` offsets; admission starts between segments and
        chunked prompt ingestion interleaves with them chunk by chunk."""
        items = sorted(workload, key=lambda r: r.arrival_s)
        results: List[RequestResult] = []
        i = 0
        t0 = time.monotonic()
        clock = lambda: time.monotonic() - t0
        while i < len(items) or self.has_work():
            now = clock()
            while i < len(items) and items[i].arrival_s <= now:
                self.submit(items[i])
                i += 1
            self.admit_ready(clock, results)
            self.step_prefill(clock, results)
            if any(s is not None for s in self._slot):
                self._step_decode(clock, results)
            elif self._pf is None and not self.queue and i < len(items):
                with span(self.telemetry, "serve.wait_arrival"):
                    time.sleep(max(0.0, min(items[i].arrival_s - now, 0.05)))
            elif self._pf is None and self.queue and self._unfundable:
                # page-budget-unfundable anchor with nothing else to do:
                # bounded exponential backoff instead of a busy spin
                n = max(self._unfundable.values())
                with span(self.telemetry, "serve.wait_arrival"):
                    time.sleep(min(0.001 * (1 << min(n, 6)), 0.05))
        results.extend(self._pending)
        self._pending.clear()
        return sorted(results, key=lambda r: r.rid)


# ---------------------------------------------------------------------------
# static-batch baseline + synthetic open-loop workloads
# ---------------------------------------------------------------------------


class StaticBatchServer:
    """The PR-1 serving pattern as a baseline: requests form fixed batches
    of ``batch_size`` in arrival order (fill the batch, then go), prompts
    are left-padded to the batch max, ``Engine.generate`` runs with
    n_new = batch max, and every request waits for the whole batch — both
    batch formation and the longest co-tenant gate each request's latency.
    Batch composition is deterministic (arrival order), so a warmup pass
    over the same workload compiles exactly the shapes a measured pass
    uses."""

    def __init__(self, engine: Engine, batch_size: int):
        self.engine = engine
        self.batch_size = batch_size

    def serve(self, workload: Sequence[Request]) -> List[RequestResult]:
        items = sorted(workload, key=lambda r: r.arrival_s)
        results: List[RequestResult] = []
        t0 = time.monotonic()
        for k in range(0, len(items), self.batch_size):
            batch = items[k:k + self.batch_size]
            # the batch launches only once its last member has arrived
            gate = max(r.arrival_s for r in batch)
            wait = gate - (time.monotonic() - t0)
            if wait > 0:
                time.sleep(wait)
            lmax = max(len(r.prompt) for r in batch)
            mat = np.full((len(batch), lmax), self.engine.pad_id, np.int32)
            lengths = np.empty((len(batch),), np.int32)
            for j, r in enumerate(batch):
                mat[j, :len(r.prompt)] = r.prompt          # right-pad
                lengths[j] = len(r.prompt)
            n = max(r.n_new for r in batch)
            admit = time.monotonic() - t0
            # per-row lengths: pad rows are zeroed from the cache and each
            # row decodes at its own depth, so shorter requests still get
            # their real generation (not pad-conditioned garbage)
            res = self.engine.generate(mat, n, lengths=lengths)
            finish = time.monotonic() - t0
            for j, r in enumerate(batch):
                # tokens only surface when the whole batch retires, so the
                # static baseline's TTFT is its full batch latency
                results.append(RequestResult(
                    r.rid, res.tokens[j, :r.n_new], len(r.prompt), r.n_new,
                    r.arrival_s, admit, finish, first_token_s=finish))
        return sorted(results, key=lambda r: r.rid)


def synthetic_workload(n_requests: int, *, rate_rps: float,
                       prompt_lens=(64, 512), n_new_range=(16, 256),
                       vocab: int = 512, seed: int = 0,
                       greedy: bool = True,
                       deadline_s: Optional[float] = None) -> List[Request]:
    """Open-loop Poisson arrival process with mixed request shapes:
    exponential inter-arrival gaps at ``rate_rps``, prompt lengths uniform
    over [prompt_lens[0], prompt_lens[1]], n_new uniform over n_new_range.
    ``deadline_s`` stamps every request with that latency budget (SLO
    workloads; None leaves them budgetless)."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for rid in range(n_requests):
        t += float(rng.exponential(1.0 / rate_rps))
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        n = int(rng.integers(n_new_range[0], n_new_range[1] + 1))
        prompt = rng.integers(1, vocab - 4, size=(plen,)).astype(np.int32)
        out.append(Request(rid, prompt, n, greedy=greedy, seed=rid,
                           arrival_s=t, deadline_s=deadline_s))
    return out


def summarize(results: Sequence[RequestResult],
              wall_s: float) -> Dict[str, float]:
    """Serving metrics: goodput (delivered new tokens per wall second),
    request latency percentiles, and time-to-first-token percentiles —
    computed over COMPLETED (``status == "ok"``) results only, so shed or
    timed-out requests don't inflate goodput; per-status counts and the
    SLO-attainment fraction (share of completed deadline-carrying results
    that finished within their budget) ride alongside.  All-ok result
    sets report exactly the pre-status numbers.  Empty ``results`` (an
    aborted serve, a smoke bench that admitted nothing) returns zeroed
    metrics instead of tracebacking on the percentile of an empty
    array."""
    counts = {f"n_{s}": 0 for s in STATUSES}
    for r in results:
        counts[f"n_{r.status}"] += 1
    ok = [r for r in results if r.status == "ok"]
    budgeted = [r for r in ok if r.deadline_s is not None]
    slo = (round(sum(r.latency_s <= r.deadline_s for r in budgeted)
                 / len(budgeted), 4) if budgeted else 1.0)
    if not ok:
        out = {"n_requests": len(results), "delivered_tokens": 0,
               "wall_s": round(wall_s, 3), "goodput_tok_s": 0.0,
               "p50_latency_s": 0.0, "p95_latency_s": 0.0,
               "mean_latency_s": 0.0, "p50_ttft_s": 0.0,
               "p95_ttft_s": 0.0}
        out.update(counts)
        out["slo_attainment"] = slo
        return out
    lats = np.asarray([r.latency_s for r in ok])
    ttfts = np.asarray([r.ttft_s for r in ok])
    toks = sum(r.n_new for r in ok)
    out = {
        "n_requests": len(results),
        "delivered_tokens": int(toks),
        "wall_s": round(wall_s, 3),
        "goodput_tok_s": round(toks / max(wall_s, 1e-9), 2),
        "p50_latency_s": round(float(np.percentile(lats, 50)), 3),
        "p95_latency_s": round(float(np.percentile(lats, 95)), 3),
        "mean_latency_s": round(float(lats.mean()), 3),
        "p50_ttft_s": round(float(np.percentile(ttfts, 50)), 3),
        "p95_ttft_s": round(float(np.percentile(ttfts, 95)), 3),
    }
    out.update(counts)
    out["slo_attainment"] = slo
    return out
