"""The graded plant (``plant["graded"]``) at a 16384-token bucket: the
program's own prompt and decode block selection, in bf16, keeps exactly
the blocks the plain reference keeps in float32, and in the reference the
last positive score kept stands at least 4% above the best score dropped.

The residual stream is a model's at d_model 4096 (Yi-6B's width): the
planted embedding of the prompt's tokens, alone (the first layer) and with
a seeded residual of three times its size on every channel but the
plant's two (a deep layer), which the program holds in bf16 and the
reference in float32.  The program's side runs its prediction path,
``_chunk_fill_pred`` and ``_dsa_chunk_attend`` over the whole prompt as one
chunk, and ``_dsa_decode`` step by step on the score caches that prefill
left; the attention they would go on to compute is left out."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, reference, run, weights

LEVELS = [round(2.0 * 1.12 ** g, 4) for g in range(15)]
PLANT = {"marker_ids": [1, 1 + len(LEVELS)],
         "graded": {"levels": LEVELS, "rows": 8}}
ARCH = dict(name="graded", n_layers=1, d_model=4096, n_heads=1,
            n_kv_heads=1, head_dim=128, d_ff=128, vocab=512,
            rope_theta=5e6, norm_eps=1e-5, dtype="bfloat16",
            param_dtype="bfloat16",
            dsa=dict(sparsity=0.9, sigma=0.25, quant_bits=4, block_q=128,
                     block_k=128, min_blocks=1, local_blocks=1,
                     decode_local=64))
MAX_LEN, PLEN, STEPS = 16384, 16000, 24
MARGIN = 0.04


def margins(scores, keep, forced, valid):
    """Per row: (last kept positive - best dropped) / last kept positive
    over the blocks selection chose among; rows where every candidate is
    kept, or none kept scores above 0, give none."""
    out = []
    for s, k, f, v in zip(scores, keep, forced, valid):
        free = v & ~f
        kept = s[free & k]
        dropped = s[free & ~k]
        kept = kept[kept > 0]
        if len(kept) and len(dropped):
            out.append((kept.min() - dropped.max()) / kept.min())
    return np.array(out)


@pytest.fixture(scope="module")
def planted():
    w = weights.make(ARCH, 7, PLANT)
    attn = jax.tree.map(lambda a: a[0], w["groups"]["b0"]["attn"])
    rng = np.random.default_rng(7)
    low = PLANT["marker_ids"][1]
    prompt = rng.integers(low, ARCH["vocab"], size=PLEN).astype(np.int32)
    generator.plant(prompt, rng, PLANT, 128)
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:PLEN] = prompt
    dec = rng.integers(low, ARCH["vocab"], size=STEPS).astype(np.int32)
    return w, attn, prompt, toks, dec


@pytest.mark.parametrize("residual", [0.0, 3.0], ids=["first", "deep"])
def test_bench_plant_graded_selection_at_16k(planted, residual,
                                             monkeypatch):
    from repro.models import attention as program
    from repro.models.common import rms_norm
    w, attn, prompt, toks, dec = planted
    d, eps = ARCH["d_model"], ARCH["norm_eps"]
    emb = np.asarray(w["embed"], np.float32)
    noise = np.random.default_rng(1).normal(
        0.0, residual, (MAX_LEN + STEPS, d)).astype(np.float32)
    noise[:, :2] = 0.0
    x = np.concatenate([emb[toks], emb[dec]]) + noise      # (S + D, d)
    cfg = run.arch_config(ARCH)
    geo = reference.geometry(ARCH, MAX_LEN, PLEN)
    assert geo["bucket"] == MAX_LEN
    bk, nb = geo["block_k"], MAX_LEN // 128

    # the program, in bf16
    monkeypatch.setattr(program.A, "dsa_chunk_block_attention",
                        lambda q, kc, vc, idx, ok, **kw: (idx, ok))
    monkeypatch.setattr(program.A, "dsa_decode_block_attention",
                        lambda q, *a, **kw: jnp.zeros_like(q))
    xb = jnp.asarray(x, jnp.bfloat16)
    h = rms_norm(xb, jnp.ones((d,), jnp.bfloat16), eps)
    kpred = weights.predictor_k(d, ARCH["dsa"]["sigma"])
    new = {"kt": jnp.zeros((1, MAX_LEN, kpred), jnp.bfloat16),
           "ktb": jnp.zeros((1, nb, kpred), jnp.bfloat16)}
    rows = jnp.arange(MAX_LEN)[None]
    q_t, kt_sel, _ = program._chunk_fill_pred(
        attn, cfg, h[None, :MAX_LEN], new, rows, rows < PLEN,
        jnp.zeros((1,), jnp.int32), None)
    heads = jnp.zeros((1, MAX_LEN, 1, 128), jnp.bfloat16)
    idx, ok = program._dsa_chunk_attend(
        cfg, program.RunFlags(mode="prefill", dsa_mode="block"), heads,
        heads, heads, q_t, kt_sel, rows, jnp.zeros((1,), jnp.int32),
        jnp.full((1,), PLEN, jnp.int32))
    got_p = np.zeros((MAX_LEN // 128, nb), bool)
    for i, (ix, o) in enumerate(zip(np.asarray(idx[0]), np.asarray(ok[0]))):
        got_p[i, ix[o]] = True
    flags = program.RunFlags(mode="decode", dsa_mode="block",
                             long_context=True, sel_probe=True)
    cache = jnp.zeros((1, MAX_LEN, 1, 128), jnp.bfloat16)
    got_d = np.zeros((STEPS, nb), bool)
    for t in range(STEPS):
        p = PLEN + t
        program._dsa_decode(attn, cfg, flags, h[None, MAX_LEN + t][None],
                            jnp.zeros((1, 1, 1, 128), jnp.bfloat16), cache,
                            cache, new, jnp.array([p]), jnp.array([p + 1]))
        sel = np.asarray(new["sel_idx"][0])[np.asarray(new["sel_ok"][0])]
        got_d[t, sel] = True

    # the reference, in float32
    mm = functools.partial(reference._mm, precision="float32")
    wa = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), attn)
    hr = reference._rms(jnp.asarray(x), jnp.ones((d,)), eps)
    qt, kt = reference.predict(wa, hr, ARCH["dsa"]["quant_bits"], mm)
    bs = reference.prompt_scores(qt[:MAX_LEN], kt[:MAX_LEN], geo)
    want_p = np.asarray(reference.prompt_keep(bs, geo))
    s_blk = reference.decode_scores(qt[MAX_LEN:], kt[:MAX_LEN],
                                    kt[MAX_LEN:], PLEN, STEPS, geo)
    kv_len = PLEN + 1 + jnp.arange(STEPS)
    want_d = np.asarray(reference.decode_keep(s_blk, kv_len, geo))

    np.testing.assert_array_equal(got_p, want_p)
    np.testing.assert_array_equal(got_d, want_d)
    qi, kj = np.arange(nb)[:, None], np.arange(nb)[None, :]
    m_p = margins(np.asarray(bs), want_p, kj > qi - 2, kj <= qi)
    jj = np.arange(nb)[None, :]
    kvl = np.asarray(kv_len)[:, None]
    m_d = margins(np.asarray(s_blk), want_d, (jj + 1) * bk > kvl - 64,
                  jj * bk < kvl)
    # selection chose among graded blocks in late query blocks and at
    # every decode step, each time by a wide margin
    assert len(m_p) >= 50 and len(m_d) == STEPS
    assert m_p.min() >= MARGIN and m_d.min() >= MARGIN, (m_p.min(),
                                                          m_d.min())


def test_bench_plant_zero_ties_break_by_index():
    """Blocks without markers score exactly 0; the program's block top-k
    and the reference's keep the lowest-index ones among them alike."""
    from repro.core import masks
    geo = {"block_k": 128, "n_kb_dec": 16, "decode_local": 64,
           "nb_keep_dec": 6, "nb_keep_pre": 5, "local_blocks": 1}
    s = np.zeros((1, 16), np.float32)
    s[0, 9] = 3.0
    idx, ok = masks.decode_block_topk_indices(
        jnp.asarray(s), 6, kv_len=jnp.array([16 * 128]), block_k=128,
        local=64)
    want = reference.decode_keep(jnp.asarray(s), jnp.array([16 * 128]), geo)
    assert sorted(np.asarray(idx[0])[np.asarray(ok[0])]) == [0, 1, 2, 3, 9,
                                                            15]
    assert sorted(np.flatnonzero(np.asarray(want[0]))) == [0, 1, 2, 3, 9, 15]
    bs = np.zeros((1, 16, 16), np.float32)
    idx, ok = masks.chunk_block_topk_indices(
        jnp.asarray(bs), 5, q_block_offset=jnp.array([0]), local_blocks=1)
    want = np.asarray(reference.prompt_keep(jnp.asarray(bs[0]), geo))
    for i in range(16):
        got = sorted(np.asarray(idx[0, i])[np.asarray(ok[0, i])])
        assert got == list(np.flatnonzero(want[i]))
    assert list(np.flatnonzero(want[12])) == [0, 1, 2, 11, 12]
