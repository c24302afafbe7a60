"""Batched serving engine: bucketed prefill + fused decode with KV caches.

Prefill bucketing: prompts are right-padded to a power-of-two bucket and
the prefill jit takes the TRUE length as a traced argument, so one compile
per (batch, bucket) serves every prompt length in the bucket.  Inside the
jit the padded logits row at ``length - 1`` is extracted and the cache is
sanitized (transformer.truncate_cache): pad rows beyond the true length
are zeroed, the DSA block-score cache ``ktb`` is rebuilt from the masked
``kt``, and the per-slot ``pos`` is set to the true length — so a bucketed
prefill leaves the cache in exactly the state an unpadded prefill would
have (modulo the zeroed tail).  Bucketing is automatically disabled for
architectures where right-padding is not a no-op for the live state
(recurrent ssm/rwkv layers, SWA ring buffers, enc-dec).

Decode fast path (``loop="scan"``, the default): the whole generation of
``n_new`` tokens after prefill — cache update, DSA prediction, attention,
and greedy/categorical sampling — is ONE jitted ``jax.lax.scan`` dispatch.
The first token is sampled from the prefill logits, so ``n_new`` tokens
need ``n_new - 1`` fused decode steps.  The scan LENGTH is also bucketed
(power of two, floor 4): varied ``n_new`` traffic hits a small fixed set
of compiled scans instead of one compile per distinct length; surplus
steps run and their tokens are truncated.  Before entering the scan the
stacked (n_groups, ...) cache is unstacked into per-layer carry leaves
(transformer.unstack_group_caches) so each step's single-token cache write
is an in-place scatter.  ``loop="python"`` keeps the legacy per-token loop
(one jitted dispatch + one host sync per token, exactly n_new - 1 steps)
as the equivalence / baseline twin; both loops thread the PRNG key
identically, so they are token-for-token identical at a fixed seed.

Recompilation contract — a new XLA compile is triggered only by a new
(batch, prompt_bucket) prefill shape, a new bucketed scan length, or a new
loop/dsa_mode/greedy flag (RunFlags is a static jit argument, so per-call
``dsa_mode`` overrides cache like any other flag); prompt length and n_new
WITHIN a bucket, and all traced values (true length, tokens, seeds,
sampling temperature), never recompile.

Throughput accounting: ``decode_steps`` counts decode steps actually
EXECUTED (the bucketed scan length on the scan path, exactly n_new - 1 on
the python path) and ``tokens_per_s = B * decode_steps / decode_s`` is the
pure decode-phase step throughput — the first token comes from prefill
logits and is not attributed to decode time on either path.  For n_new=1
no decode step runs and tokens_per_s is reported as 0.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import (compute_context, current_mesh,
                                        make_serving_rules, replicate_put,
                                        serving_tp_issues, shard_put_batch,
                                        shard_put_tree)
from repro.inference.config import ServingConfig, resolve_config
from repro.models.attention import RunFlags
from repro.models.transformer import (cache_specs, decode_step, forward,
                                      init_cache, model_param_specs,
                                      truncate_cache, unstack_group_caches)

# floor for power-of-two buckets: prompt lengths and scan step counts are
# rounded up to at least this (tiny shapes all share one compile)
PROMPT_BUCKET_FLOOR = 16
STEP_BUCKET_FLOOR = 4


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= n (and >= floor).  Static/host-side."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


def can_bucket_prompts(cfg: ArchConfig) -> bool:
    """Right-padded prefill is only sound when pad rows can be masked out
    afterwards: recurrent state (mamba/rwkv) and SWA ring buffers absorb
    pad tokens irreversibly, and enc-dec decoders use absolute sinusoidal
    positions over the padded length."""
    return (cfg.mamba is None and cfg.rwkv is None
            and cfg.swa_window == 0 and not cfg.enc_dec)


def can_page(cfg: ArchConfig) -> bool:
    """Paged resident caches (block-table indirection over a shared
    physical page pool, inference.scheduler.ContinuousEngine(paged=True))
    are supported where every per-slot cache leaf is either a page pool or
    a per-slot scalar: recurrent state (mamba/rwkv) and SWA ring buffers
    have no token-row geometry to page, enc-dec / cross-attn decoders
    carry per-slot encoder caches, and MLA's latent c_kv/k_rope leaves
    keep the dense layout (paging them buys little — they are already the
    compressed cache)."""
    return (cfg.mamba is None and cfg.rwkv is None and cfg.swa_window == 0
            and not cfg.enc_dec and cfg.mla is None
            and cfg.cross_attn_period == 0)


def can_quantize(cfg: ArchConfig) -> bool:
    """Mixed-precision serving (ServingConfig select_dtype/kv_quant)
    covers the standard GQA attention cache layout — the same envelope as
    paging: recurrent state (mamba/rwkv) and SWA ring buffers carry no
    quantized token rows, enc-dec / cross-attn decoders hold encoder
    caches outside the scheme, and MLA's latent c_kv/k_rope leaves are
    already the compressed cache."""
    return can_page(cfg)


def can_chunk_prefill(cfg: ArchConfig, dsa_mode: str = "off",
                      moe_dense: bool = False) -> bool:
    """Chunked (interleavable) admission prefill is supported wherever it
    is token-exact against the whole-prompt bucketed prefill: everything
    prompt bucketing covers, MINUS MoE archs (prefill routes tokens
    through the capacity-dispatch path while chunk steps run the
    decode-dense expert path — same math, different summation order),
    cross-attn decoders (no image side-channel at admission), and
    DSA-over-MLA (no predicted-key cache to resume per chunk).

    ``moe_dense`` (Engine(moe_prefill="dense")) re-admits MoE archs:
    whole-prompt prefill then routes the decode-dense expert path too, so
    prefill and chunk steps are bitwise token-exact again."""
    return (can_bucket_prompts(cfg) and (cfg.moe is None or moe_dense)
            and cfg.cross_attn_period == 0
            and not (cfg.mla is not None and dsa_mode != "off"))


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray           # (B, n_new) delivered tokens
    prefill_s: float
    decode_s: float
    tokens_per_s: float          # B * decode_steps / decode_s (0 if no steps)
    decode_dispatches: int = 0   # jitted decode dispatches issued
    decode_steps: int = 0        # decode steps EXECUTED (bucketed on scan)
    spec_rounds: int = 0         # verify rounds (speculative path only)
    spec_accept_hist: Optional[List[int]] = None  # rounds by emitted count


def _ro_view(a: np.ndarray, n) -> np.ndarray:
    """Read-only prefix view of a history buffer.  Draft proposers are
    user code in the correctness-free zone — a writable view would let a
    proposer that scribbles on (or retains) its contexts silently corrupt
    the live per-slot history the next rounds draft from."""
    v = a[:int(n)]
    v.flags.writeable = False
    return v


def _sample(logits, key, greedy: bool, temperature=1.0):
    """Sample the next token from (B, V) logits; returns ((B,1) i32, key).
    Greedy never consumes the key — the per-request key chain is therefore
    identical across engines and the continuous scheduler.  ``temperature``
    scales sampled logits only; 1.0 divides exactly (IEEE), so the default
    is bit-identical to the unscaled chain."""
    if greedy:
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32), key

    def _draw(k, x):
        k2, sk = jax.random.split(k)
        return (jax.random.categorical(sk, x)[:, None]
                .astype(jnp.int32), k2)

    lg = logits / temperature
    mesh = current_mesh()
    if mesh is None:
        return _draw(key, lg)
    # Under a mesh the whole rng chain (split + gumbel draw) runs inside a
    # fully-REPLICATED shard_map: each device executes the full-size draw
    # over the whole vocabulary locally, the same program as unsharded.
    # jax's threefry is partitionable by default
    # (jax_threefry_partitionable), so a partitioned draw would give the
    # same bits too; the replicated draw is kept as the one path that the
    # token-exactness pins of sharded serving were taken on, at the cost of
    # every device drawing over the full vocabulary.
    p_rep = jax.sharding.PartitionSpec()
    return jax.shard_map(_draw, mesh=mesh,
                         in_specs=(p_rep, p_rep),
                         out_specs=(p_rep, p_rep),
                         check_vma=False)(key, lg)


class Engine:
    def __init__(self, cfg: ArchConfig, params, *,
                 config: Optional[ServingConfig] = None, **kw):
        """Legacy keyword arguments (max_len=, dsa_mode=, ...) are
        accepted and forwarded into the config — bitwise identical to the
        pre-config constructor; prefer ``config=ServingConfig(...)`` in
        new call sites."""
        c = resolve_config(config, kw)      # validates all choice knobs
        self.config = c
        self.cfg = cfg
        if (c.select_dtype != "float32" or c.kv_quant) and \
                not can_quantize(cfg):
            raise ValueError(
                f"select_dtype={c.select_dtype!r}/kv_quant={c.kv_quant!r} "
                f"unsupported for arch {cfg.name!r} (see "
                f"engine.can_quantize)")
        if c.select_dtype != "float32" and not c.long_context:
            raise ValueError("select_dtype quantizes the DSA predicted-key "
                             "caches — requires long_context=True")
        # mesh-sharded serving: caches/carries shard over "data" (SPMD data
        # parallelism over the batch/slots axis), and on a 2-D
        # ("data", "model") mesh whose model dims divide, weights ALSO
        # shard over "model" (tensor parallelism: Q/K/V/O over heads,
        # MLP/experts over mlp/expert, embedding over vocab) with the KV
        # cache head-sharded alongside — GSPMD inserts the post-matmul
        # all-reduces from the activation constraints already in the model
        # layers, and generation stays token-exact vs unsharded (the
        # reduction order is fixed per mesh).  An indivisible-TP config
        # falls back to replicated weights gracefully, mirroring the
        # slots-vs-data behavior; mesh=None (the default) leaves every
        # dispatch exactly as before.
        self.mesh = c.mesh
        self.shard_rules = None
        self.tp = 1
        if c.mesh is not None:
            tp = int(dict(c.mesh.shape).get("model", 1))
            tp_ok = tp > 1 and not serving_tp_issues(cfg, tp)
            self.shard_rules = (c.shard_rules if c.shard_rules is not None
                                else make_serving_rules(
                                    long_context=c.long_context, tp=tp_ok))
            if tp_ok or (c.shard_rules is not None and tp > 1):
                params = shard_put_tree(params, model_param_specs(cfg),
                                        c.mesh, self.shard_rules)
                self.tp = tp
            else:
                params = replicate_put(params, c.mesh)
        self.params = params
        self.max_len = c.max_len
        self.loop = c.loop
        self.pad_id = c.pad_id
        self.bucket_prompts = c.prompt_buckets and can_bucket_prompts(cfg)
        self.bucket_steps = c.step_buckets
        # moe_prefill="dense": route prefill through the decode-dense
        # expert path so prefill/chunk/decode are all token-exact (enables
        # chunked admission + speculation for MoE archs)
        self.moe_dense = c.moe_prefill == "dense" and cfg.moe is not None
        self.prefill_flags = RunFlags(mode="prefill", dsa_mode=c.dsa_mode,
                                      with_mse=False,
                                      long_context=c.long_context,
                                      moe_dense=self.moe_dense,
                                      select_dtype=c.select_dtype,
                                      kv_quant=c.kv_quant)
        self.decode_flags = RunFlags(mode="decode", dsa_mode=c.dsa_mode,
                                     with_mse=False,
                                     long_context=c.long_context,
                                     select_dtype=c.select_dtype,
                                     kv_quant=c.kv_quant)
        self.cache_dtype = c.cache_dtype
        self._spec_decoders: Dict[int, "object"] = {}

        def _prefill(params, batch, caches, lengths, flags: RunFlags):
            logits, _, caches = forward(params, cfg, flags, batch,
                                        caches=caches)
            caches = truncate_cache(cfg, caches, lengths)
            idx = (lengths - 1)[:, None, None]       # per-row last position
            last = jnp.take_along_axis(logits, idx, axis=1)
            return last, caches

        def _decode(params, tok, caches, flags: RunFlags):
            return decode_step(params, cfg, flags, tok, caches)

        def _decode_loop(params, tok0, caches, key, temperature,
                         n_steps: int, greedy: bool, flags: RunFlags):
            """Fused on-device generation: scan n_steps decode steps."""
            def body(carry, _):
                tok, caches, key = carry
                logits, caches = decode_step(params, cfg, flags, tok, caches)
                nxt, key = _sample(logits[:, -1], key, greedy, temperature)
                return (nxt, caches, key), nxt[:, 0]

            (tok, caches, key), toks = jax.lax.scan(
                body, (tok0, caches, key), None, length=n_steps)
            return toks.swapaxes(0, 1), caches      # (B, n_steps)

        # RunFlags is frozen/hashable, so per-call flag overrides (e.g. a
        # per-request dsa_mode) jit-cache like any other static argument
        self._prefill = jax.jit(_prefill, static_argnames=("flags",),
                                donate_argnums=(2,))
        self._decode = jax.jit(_decode, static_argnames=("flags",),
                               donate_argnums=(2,))
        self._decode_loop = jax.jit(
            _decode_loop, static_argnames=("n_steps", "greedy", "flags"),
            donate_argnums=(2,))
        # compile-event observability: with telemetry enabled every jitted
        # entry point is wrapped in a host-side watcher that records each
        # distinct (program, shape-signature) dispatch; the wrapper forwards
        # calls unchanged (donation included), and telemetry=None (default)
        # leaves the bare jits in place — bitwise-inert
        self.telemetry = c.telemetry
        if self.telemetry is not None:
            tel = self.telemetry
            self._prefill = tel.wrap_jit("prefill", self._prefill)
            self._decode = tel.wrap_jit("decode", self._decode)
            self._decode_loop = tel.wrap_jit("decode_loop",
                                             self._decode_loop)

    # -- mesh placement -----------------------------------------------------

    def _ctx(self):
        """(mesh, rules) context for a dispatch — no-op without a mesh."""
        return compute_context(self.mesh, self.shard_rules)

    def put_batch(self, x):
        """Land a batch-axis-0 carry on the serving mesh (identity without
        one) — always re-placed so jit sees ONE stable input sharding."""
        if self.mesh is None:
            return jnp.asarray(x)
        return shard_put_batch(x, self.mesh, self.shard_rules)

    def put_cache(self, caches, specs):
        if self.mesh is None:
            return caches
        return shard_put_tree(caches, specs, self.mesh, self.shard_rules)

    def weight_bytes_per_device(self) -> int:
        """Resident weight bytes ON ONE DEVICE (shard shapes, not global
        shapes) — the quantity tensor parallelism reduces ~1/tp.  With
        replicated weights (mesh=None or dp-only) this equals the full
        parameter footprint; benchmarks/table_serve.py gates the tp-vs-
        replicated ratio on it (pure byte counts, deterministic)."""
        total = 0
        for x in jax.tree.leaves(self.params):
            shape = tuple(x.shape)
            sh = getattr(x, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                shape = sh.shard_shape(shape)
            n = 1
            for d in shape:
                n *= int(d)
            total += n * x.dtype.itemsize
        return int(total)

    # -- prefill ------------------------------------------------------------

    def prompt_bucket(self, prompt_len: int) -> int:
        if not self.bucket_prompts:
            return prompt_len
        return min(pow2_bucket(prompt_len, PROMPT_BUCKET_FLOOR), self.max_len)

    def run_flags(self, mode: str, dsa_mode: Optional[str] = None
                  ) -> RunFlags:
        """The engine's prefill/decode flags, optionally with a per-call
        ``dsa_mode`` override (per-request modes in the scheduler)."""
        base = self.prefill_flags if mode == "prefill" else self.decode_flags
        if dsa_mode is None or dsa_mode == base.dsa_mode:
            return base
        return dataclasses.replace(base, dsa_mode=dsa_mode)

    def prefill(self, prompts: np.ndarray,
                extras: Optional[Dict[str, np.ndarray]] = None,
                cache_len: Optional[int] = None,
                lengths: Optional[np.ndarray] = None,
                dsa_mode: Optional[str] = None
                ) -> Tuple[jax.Array, Dict, float]:
        """Bucketed prefill of a (B, L) prompt batch into a fresh cache.

        Returns (last_logits (B,1,V), caches, prefill_seconds).  The cache
        is allocated at ``cache_len`` (default: engine max_len) — the
        continuous scheduler passes the prompt bucket here and zero-extends
        at slot insertion.  ``lengths`` (B,) gives per-row true prompt
        lengths for batched admission prefill (rows right-padded to a
        common width); default: every row is full width.  ``dsa_mode``
        overrides the engine's DSA execution path for this call.
        """
        b, s = np.asarray(prompts).shape
        padded = self.prompt_bucket(s)
        assert padded >= s, (padded, s)
        if padded > s:
            pad = np.full((b, padded - s), self.pad_id, np.int32)
            prompts = np.concatenate([np.asarray(prompts, np.int32), pad], 1)
        if lengths is None:
            lengths = np.full((b,), s, np.int32)
        caches = init_cache(self.cfg, b, cache_len or self.max_len,
                            self.decode_flags, dtype=self.cache_dtype)
        if self.mesh is not None:
            caches = self.put_cache(caches, cache_specs(self.cfg, caches,
                                                        self.decode_flags))
        batch = {"tokens": self.put_batch(prompts)}
        if extras:
            batch.update({k: self.put_batch(v) for k, v in extras.items()})
        t0 = time.monotonic()
        with self._ctx():
            last, caches = self._prefill(self.params, batch, caches,
                                         self.put_batch(
                                             np.asarray(lengths, np.int32)),
                                         flags=self.run_flags("prefill",
                                                              dsa_mode))
        last.block_until_ready()
        return last, caches, time.monotonic() - t0

    # -- generation ---------------------------------------------------------

    def _spec_decoder(self, k: int):
        from repro.inference.speculative import SpeculativeDecoder
        if k not in self._spec_decoders:
            self._spec_decoders[k] = SpeculativeDecoder(
                self.cfg, k, telemetry=self.telemetry)
        return self._spec_decoders[k]

    def _generate_spec(self, prompts, n_new: int, spec: int, draft, extras,
                       greedy: bool, seed: int, lengths, temperature: float,
                       dsa_mode: Optional[str]) -> GenerationResult:
        """Speculative generation: draft K tokens per row from ``draft``
        (default: self-drafting NGramProposer), verify + commit them in
        one fused dispatch per round (repro.inference.speculative), loop
        until every row has its n_new tokens.  Token-exact vs the plain
        paths: greedy at any batch size; sampled at B=1 (per-row chains —
        see the speculative module docstring)."""
        from repro.inference.speculative import NGramProposer, can_speculate
        mode = dsa_mode if dsa_mode is not None else self.decode_flags.dsa_mode
        if not can_speculate(self.cfg, mode, spec):
            raise ValueError(
                f"spec={spec} unsupported for arch {self.cfg.name!r} at "
                f"dsa_mode {mode!r} (see speculative.can_speculate)")
        prompts = np.asarray(prompts, np.int32)
        b = prompts.shape[0]
        logits, caches, t_prefill = self.prefill(prompts, extras,
                                                 lengths=lengths,
                                                 dsa_mode=dsa_mode)
        dflags = dataclasses.replace(self.run_flags("decode", dsa_mode),
                                     spec_verify=True)
        temp = jnp.asarray(temperature, jnp.float32)
        key = jax.random.PRNGKey(seed)
        t0 = time.monotonic()
        # _ctx(): under a mesh the eager draw must see the mesh so _sample
        # runs it in its replicated shard_map
        with self._ctx():
            tok, key = _sample(logits[:, -1], key, greedy, temp)
        if lengths is None:
            lengths = np.full((b,), prompts.shape[1], np.int32)
        tok_np = np.asarray(tok)
        # incremental per-row history buffers (prompt + every emitted
        # token), appended in place — proposers get O(new tokens) views,
        # not an O(T) rebuild per verify round (the scheduler's
        # _SlotState.history, mirrored here)
        hists, hlens = [], np.empty((b,), np.int64)
        for i in range(b):
            plen = int(lengths[i])
            hb = np.empty((plen + n_new,), np.int32)
            hb[:plen] = prompts[i, :plen]
            hb[plen] = tok_np[i, 0]
            hists.append(hb)
            hlens[i] = plen + 1
        out_rows = [[int(tok_np[i, 0])] for i in range(b)]
        remaining = np.full((b,), n_new - 1, np.int32)
        active = remaining > 0
        keys = np.tile(np.asarray(key), (b, 1))
        greedy_v = np.full((b,), greedy, bool)
        temps = np.full((b,), temperature, np.float32)
        caches = unstack_group_caches(caches)
        sd = self._spec_decoder(spec)
        proposer = draft if draft is not None else NGramProposer()
        accept_hist = [0] * (spec + 1)
        rounds = 0
        while active.any():
            drafts = proposer.propose(
                [_ro_view(hists[i], hlens[i]) for i in range(b)], spec)
            with self._ctx():
                tok, caches, keys, nxt, emit, remaining_d, active_d = \
                    sd.verify(self.params, tok, self.put_batch(drafts),
                              caches, self.put_batch(keys),
                              self.put_batch(active),
                              self.put_batch(greedy_v),
                              self.put_batch(temps),
                              self.put_batch(remaining), flags=dflags)
            emit_np, nxt_np = np.asarray(emit), np.asarray(nxt)
            for i in range(b):
                e = int(emit_np[i])
                if e:
                    seg = nxt_np[i, :e].astype(np.int32)
                    out_rows[i].extend(seg.tolist())
                    hists[i][hlens[i]:hlens[i] + e] = seg
                    hlens[i] += e
                    accept_hist[e - 1] += 1
            remaining = np.asarray(remaining_d)
            active = np.asarray(active_d)
            rounds += 1
        toks = np.asarray([r[:n_new] for r in out_rows], np.int32)
        t_decode = time.monotonic() - t0
        emitted = b * (n_new - 1)        # decode-phase tokens (tok0 excluded)
        tps = emitted / max(t_decode, 1e-9) if emitted else 0.0
        return GenerationResult(toks, t_prefill, t_decode, tps,
                                decode_dispatches=rounds,
                                decode_steps=rounds * (spec + 1),
                                spec_rounds=rounds,
                                spec_accept_hist=accept_hist)

    def generate(self, prompts: np.ndarray, n_new: int,
                 extras: Optional[Dict[str, np.ndarray]] = None,
                 greedy: bool = True, seed: int = 0,
                 lengths: Optional[np.ndarray] = None,
                 temperature: float = 1.0,
                 dsa_mode: Optional[str] = None,
                 spec: int = 0, draft=None) -> GenerationResult:
        """``lengths`` (B,): per-row true prompt lengths for a ragged batch
        whose rows are RIGHT-padded to a common width — pad rows are zeroed
        from the cache and each row prefills/decodes at its own depth (the
        per-slot ``pos``), so every row's generation is what it would be
        unpadded.  Default: all rows full width.  ``temperature`` scales
        sampled (non-greedy) logits; ``dsa_mode`` overrides the engine's
        DSA execution path for this call (same cache layout required —
        ``long_context`` stays the engine's).  ``spec=K`` switches to
        speculative draft-and-verify decoding (K draft tokens per fused
        verify dispatch, proposer ``draft``): token-exact vs spec=0 for
        greedy at any batch size and for sampling at B=1 — a SAMPLED B>1
        batch draws per-row B=1 chains instead of the plain path's
        shared-key batched draw, so rows match their solo generations,
        not the batched spec=0 call (the serving engines replay per-slot
        B=1 chains, so requests are unaffected; see
        repro.inference.speculative)."""
        assert n_new >= 1, "generate() needs n_new >= 1"
        # reject an over-long request up front with a clear error instead
        # of failing deep inside prefill/decode once the cache overflows
        plen = (int(np.asarray(prompts).shape[1]) if lengths is None
                else int(np.max(lengths)))
        if plen == 0 or (lengths is not None
                         and int(np.min(lengths)) < 1):
            raise ValueError("empty prompt: decode needs at least one "
                             "context token per row")
        if plen + n_new > self.max_len:
            raise ValueError(
                f"prompt_len ({plen}) + n_new ({n_new}) exceeds the "
                f"engine max_len ({self.max_len}) — raise max_len or "
                f"shorten the request")
        if spec:
            return self._generate_spec(prompts, n_new, spec, draft, extras,
                                       greedy, seed, lengths, temperature,
                                       dsa_mode)
        b = np.asarray(prompts).shape[0]
        logits, caches, t_prefill = self.prefill(prompts, extras,
                                                 lengths=lengths,
                                                 dsa_mode=dsa_mode)
        dflags = self.run_flags("decode", dsa_mode)
        temp = jnp.asarray(temperature, jnp.float32)
        key = jax.random.PRNGKey(seed)
        t0 = time.monotonic()
        # token 1 comes from the prefill logits: n_new tokens need exactly
        # n_new - 1 decode steps (the scan path may execute a few more to
        # stay on a bucketed scan length; surplus tokens are truncated).
        # _ctx() so _sample finds the mesh on this EAGER call too and runs
        # the draw in its replicated shard_map
        with self._ctx():
            tok, key = _sample(logits[:, -1], key, greedy, temp)
        dispatches = 0
        steps_exec = 0
        if self.loop == "scan":
            if n_new > 1:
                steps = n_new - 1
                steps_exec = (pow2_bucket(steps, STEP_BUCKET_FLOOR)
                              if self.bucket_steps else steps)
                # per-layer cache leaves: in-place slot updates inside the
                # scan instead of restacking the whole KV cache per step
                caches = unstack_group_caches(caches)
                with self._ctx():
                    rest, caches = self._decode_loop(self.params, tok,
                                                     caches, key, temp,
                                                     n_steps=steps_exec,
                                                     greedy=greedy,
                                                     flags=dflags)
                dispatches = 1
                toks = jnp.concatenate([tok, rest], axis=1)[:, :n_new]
            else:
                toks = tok
        else:
            out: List[jax.Array] = [tok]
            for _ in range(n_new - 1):
                with self._ctx():
                    logits, caches = self._decode(self.params, tok, caches,
                                                  flags=dflags)
                dispatches += 1
                with self._ctx():
                    tok, key = _sample(logits[:, -1], key, greedy, temp)
                out.append(np.asarray(tok))
            steps_exec = n_new - 1
            toks = jnp.concatenate(out, axis=1)
        toks.block_until_ready()
        t_decode = time.monotonic() - t0
        tps = b * steps_exec / max(t_decode, 1e-9) if steps_exec else 0.0
        return GenerationResult(np.asarray(toks), t_prefill, t_decode, tps,
                                decode_dispatches=dispatches,
                                decode_steps=steps_exec)
