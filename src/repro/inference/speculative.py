"""Speculative decoding: draft-and-verify multi-token decode segments.

The serving stack's decode segments produce exactly ONE token per fused
step: every step is a full traversal of the model (weights + cache read)
for a single new token per slot.  This module multiplies tokens per
dispatch instead: a cheap DRAFT PROPOSER guesses K tokens per slot, and
ONE ``transformer.verify_step`` dispatch scores all K drafts against the
resident cache — the chunk-append path (PR 3) generalized to per-row
DECODE-exact attention — so one model traversal can commit up to K+1
tokens (the Energon-style amortization of memory-bound decode the
ROADMAP calls out).

Exactness contract (the part that makes this a drop-in serving feature):
speculative decode is BITWISE token-exact against plain sequential decode
at the same seed/temperature/dsa_mode — not merely distribution-
preserving.  Acceptance is by sampled-token match, not by Leviathan-style
probability-ratio rejection sampling: at verify row i the engine draws
the token the sequential chain WOULD have drawn (greedy argmax, or
``jax.random.categorical`` on the row's logits with the per-slot PRNG
chain advanced exactly as the fused segment advances it) and accepts the
draft only if it equals that draw.  Row i's logits are bitwise the
sequential decode step's logits given the accepted prefix (verify-path
numerics in ``models/attention._apply_verify``), so by induction the
emitted tokens — the accepted prefix plus the one corrected/bonus token —
are exactly the sequential run's tokens, and the rewound key chain state
equals the sequential chain after the same number of draws.  Rejected
draft rows are rolled back by ``transformer.commit_chunk``
(write-then-invalidate with a deterministic ktb block rebuild).

Per verify round a slot emits between 1 (first draft rejected: the
corrected token) and K+1 (all drafts accepted + the bonus token) tokens.
Compilation: one verify-chunk compile per (slots, K) per dsa_mode in use;
K is static per engine/decoder.  Drafting never affects correctness —
only the acceptance rate — so any proposer is safe.

Sampling exactness scope: per-slot chains replay ``Engine.generate``'s
B=1 chain (the serving anchor, like the continuous scheduler).  Greedy
speculation is exact at any batch size; sampled speculation in a B>1
static ``Engine.generate`` call matches the per-row B=1 chains rather
than the shared-key batched chain (``jax.random.categorical`` noise
depends on the batch shape), which is the same contract the continuous
engine already pins.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models.attention import DECODE_LOCAL, RunFlags
from repro.models.transformer import commit_chunk, forward, verify_step


def can_speculate(cfg: ArchConfig, dsa_mode: str = "off", k: int = 1
                  ) -> bool:
    """Speculative verify is supported wherever a chunk-append with
    per-row decode numerics is token-exact: non-wrapping caches only (no
    recurrent ssm/rwkv state to roll back, no SWA ring, no enc-dec /
    cross-attn decoders), no DSA-over-MLA (no predicted-key cache —
    mirroring ``can_chunk_prefill``), and on the DSA block paths the
    verify chunk (K+1 rows) must fit inside the DECODE_LOCAL force-keep
    window so the deferred ``ktb`` update is never read stale.  MoE archs
    ARE supported: decode steps and verify chunks both route the
    decode-dense expert path."""
    return (cfg.mamba is None and cfg.rwkv is None and cfg.swa_window == 0
            and not cfg.enc_dec and cfg.cross_attn_period == 0
            and not (cfg.mla is not None and dsa_mode != "off")
            and (dsa_mode == "off" or k + 1 <= DECODE_LOCAL))


# ---------------------------------------------------------------------------
# draft proposers
# ---------------------------------------------------------------------------


class DraftProposer:
    """Protocol for draft proposers (host-side, correctness-free zone).

    ``propose(contexts, k)`` receives each slot's full token history
    (prompt + every emitted token, the last entry being the pending token
    the next verify row re-scores) and returns (B, k) int32 draft
    continuations.  Proposals only move the ACCEPTANCE RATE — a bad
    proposer degrades speculative decode to one token per round, never to
    wrong tokens."""

    def propose(self, contexts, k: int) -> np.ndarray:
        raise NotImplementedError


class NGramProposer(DraftProposer):
    """Self-drafting n-gram lookup (prompt-lookup decoding): match the
    longest trailing n-gram (n from ``max_n`` down to ``min_n``) earlier
    in the context and propose the k tokens that followed its most recent
    occurrence.  Free of any extra model: the draft cost is a numpy scan
    of the history.  Strong on repetitive / extractive workloads (long
    contexts that quote themselves), weak on high-entropy text — where it
    simply degrades to ~1 token per verify."""

    def __init__(self, max_n: int = 3, min_n: int = 1):
        assert 1 <= min_n <= max_n
        self.max_n = max_n
        self.min_n = min_n

    def _one(self, ctx: np.ndarray, k: int) -> np.ndarray:
        fill = np.full((k,), ctx[-1] if ctx.size else 0, np.int32)
        n_hi = min(self.max_n, ctx.size - 1)
        for n in range(n_hi, self.min_n - 1, -1):
            pat = ctx[ctx.size - n:]
            n_start = ctx.size - n          # exclude the suffix itself
            if n_start <= 0:
                continue
            win = np.lib.stride_tricks.sliding_window_view(ctx, n)[:n_start]
            hits = np.flatnonzero((win == pat).all(axis=1))
            if hits.size:
                i = int(hits[-1])           # most recent occurrence
                cont = ctx[i + n:i + n + k]
                if cont.size:
                    out = fill.copy()
                    out[:cont.size] = cont
                    return out
        return fill

    def propose(self, contexts, k: int) -> np.ndarray:
        out = np.empty((len(contexts), k), np.int32)
        for r, ctx in enumerate(contexts):
            out[r] = self._one(np.asarray(ctx, np.int32), k)
        return out


class DraftModelProposer(DraftProposer):
    """A small draft ``Transformer`` sharing the tokenizer/vocab: greedy
    continuation over a trailing ``window`` of each context (stateless —
    no draft KV cache to keep coherent with slot churn, at the price of a
    window re-read per proposed token).  One jitted extend-by-one per
    proposed token at a fixed (B, window+k) shape, so drafting never
    recompiles.  The window buffer STAYS ON DEVICE between the k greedy
    steps — one host->device upload per round and one download at the end
    (each step's argmax is scattered in on device via ``.at[rows,
    lens].set``), instead of re-uploading the whole (B, window+k) buffer k
    times per round.  Quality-only: draft positions restart at 0 inside
    the window, which shifts RoPE phases vs the target model but can only
    lower acceptance, never correctness."""

    def __init__(self, cfg: ArchConfig, params, window: int = 64):
        self.cfg = cfg
        self.params = params
        self.window = int(window)
        flags = RunFlags(mode="train", dsa_mode="off", with_mse=False)

        def _extend(params, toks, lengths):
            logits, _, _ = forward(params, cfg, flags, {"tokens": toks})
            idx = (lengths - 1)[:, None, None]
            last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
            nxt = jnp.argmax(last, -1).astype(jnp.int32)
            rows = jnp.arange(toks.shape[0])
            return toks.at[rows, lengths].set(nxt), lengths + 1

        self._extend = jax.jit(_extend, donate_argnums=(1,))

    def propose(self, contexts, k: int) -> np.ndarray:
        b, w = len(contexts), self.window
        buf = np.zeros((b, w + k), np.int32)
        lens = np.empty((b,), np.int32)
        for r, ctx in enumerate(contexts):
            ctx = np.asarray(ctx, np.int32)
            m = min(ctx.size, w)
            if m:
                buf[r, :m] = ctx[-m:]
            lens[r] = max(m, 1)
        start = lens.copy()
        dbuf, dlens = jnp.asarray(buf), jnp.asarray(lens)  # ONE upload
        for _ in range(k):
            dbuf, dlens = self._extend(self.params, dbuf, dlens)
        out = np.asarray(dbuf)                             # ONE download
        return np.stack([out[r, start[r]:start[r] + k] for r in range(b)])


# ---------------------------------------------------------------------------
# the verify engine layer
# ---------------------------------------------------------------------------


def _make_verify(cfg: ArchConfig):
    """Build the fused verify+accept+commit step (one jit dispatch).

    (tok (B,1), drafts (B,K)) -> verify chunk [tok, d_1..d_K] of C = K+1
    rows; row i's logits draw the sequential chain's token for position i
    (per-slot split + categorical, or argmax); ``m`` leading draft matches
    commit rows [0, m+1) and emit tokens nxt_0..nxt_m (clamped by the
    remaining budget); the rejected tail rolls back via ``commit_chunk``
    and the key chain rewinds to the state after exactly ``emit`` draws.
    """

    def fn(params, tok, drafts, caches, keys, active, greedy, temps,
           remaining, flags: RunFlags):
        b, k = drafts.shape
        c = k + 1
        chunk = jnp.concatenate([tok, drafts], axis=1)       # (B, C)
        logits, caches = verify_step(params, cfg, flags, chunk, caches,
                                     active=active)
        nxt_g = jnp.argmax(logits, -1).astype(jnp.int32)     # (B, C)

        def chain(ks_carry, lg_i):
            # rows shard over "data", vocab replicated per row, as in the
            # scheduler's segment sampling; no-op without a mesh
            lg_i = shard(lg_i, "batch", None)
            kk = jax.vmap(jax.random.split)(ks_carry)        # (B, 2, 2)
            smp = jax.vmap(jax.random.categorical)(
                kk[:, 1], lg_i / temps[:, None])
            return kk[:, 0], (smp.astype(jnp.int32), kk[:, 0])

        _, (nxt_s, key_states) = jax.lax.scan(chain, keys,
                                              logits.swapaxes(0, 1))
        nxt_s = nxt_s.swapaxes(0, 1)                         # (B, C)
        key_states = key_states.swapaxes(0, 1)               # (B, C, 2)
        nxt = jnp.where(greedy[:, None], nxt_g, nxt_s)
        matches = (nxt[:, :k] == drafts).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)    # (B,)
        emit = jnp.minimum(m + 1, remaining)
        emit = jnp.where(active, emit, 0)
        caches = commit_chunk(cfg, caches, emit, c, active=active)
        idx = jnp.maximum(emit - 1, 0)
        live = active & (emit > 0)
        new_tok = jnp.take_along_axis(nxt, idx[:, None], axis=1)
        new_tok = jnp.where(live[:, None], new_tok, tok)
        sel_keys = jnp.take_along_axis(key_states,
                                       idx[:, None, None], axis=1)[:, 0]
        new_keys = jnp.where((greedy | ~live)[:, None], keys, sel_keys)
        remaining = remaining - emit
        active = active & (remaining > 0)
        return new_tok, caches, new_keys, nxt, emit, remaining, active

    return fn


class SpeculativeDecoder:
    """Jitted draft-verify step for a fixed K (static per decoder).

    Shared by ``Engine.generate(spec=K)`` and the continuous engine's
    speculative segments; compiles once per (batch/slots, K, dsa_mode)
    shape-and-flag set.  Stateless apart from the jit cache — all decode
    state (pending token, caches, per-slot key chains, budgets) is passed
    through, so one decoder serves any number of generations."""

    def __init__(self, cfg: ArchConfig, k: int, telemetry=None):
        assert k >= 1, "speculative decoding needs at least one draft token"
        self.cfg = cfg
        self.k = k
        self._verify = jax.jit(_make_verify(cfg),
                               static_argnames=("flags",),
                               donate_argnums=(3,))
        if telemetry is not None:
            # compile-event observability (inference.telemetry): record
            # every distinct verify shape signature; forwards unchanged
            self._verify = telemetry.wrap_jit("verify", self._verify)

    def verify(self, params, tok, drafts, caches, keys, active, greedy,
               temps, remaining, flags: RunFlags):
        """One fused verify round.  Returns (tok', caches', keys',
        sampled_tokens (B, K+1), emit (B,), remaining', active') — the
        caller collects ``sampled_tokens[i, :emit[i]]`` per row."""
        assert flags.spec_verify and flags.mode == "decode"
        drafts = jnp.asarray(drafts, jnp.int32)
        assert drafts.shape[-1] == self.k, (drafts.shape, self.k)
        return self._verify(params, jnp.asarray(tok), drafts, caches,
                            jnp.asarray(keys), jnp.asarray(active),
                            jnp.asarray(greedy),
                            jnp.asarray(temps, jnp.float32),
                            jnp.asarray(remaining, jnp.int32), flags=flags)
