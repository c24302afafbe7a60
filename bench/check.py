"""The comparison that decides a run's ``correct``.

After the window has closed and the serving engine is freed, the plain
reference (the module the configuration names, by default
``bench/reference.py``) is run over each compared request (up
to ``check.sample`` completed requests, drawn from the seed, the longest
prompt always among them), teacher-forced over the tokens the engine
served: it gives the logits at every position that produced a served
token, the prompt's last row (chunked prefill) and then each decode step.
The gap of a served token is how far its logit lies below the reference's
best at that position, in standard deviations of that row's logits (0
where the served token is the reference's first choice).  Compared, each
against the cell's own limit (``bench/cells/<cell>.json``):

- ``widest_gap``: the largest gap over every served token compared;
- ``mean_gap``: the mean gap over them.

The cell's weights and prompts are planted (``bench/weights.py``) so that
DSA block selection has one answer at every decode step, which bf16
rounding cannot flip: a served token that differs from the reference's
then comes from the program, in chunked prefill, the decode step, the
selection or the kernels.  A compared request that did not end ``ok``, or
whose tokens are not ``n_new`` ids in the vocabulary, makes the run
incorrect outright.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference


def sample(results, n: int, seed: int) -> List:
    """Up to ``n`` completed results: the longest prompt, then a seeded
    draw of the rest."""
    done = sorted((r for r in results if r.status == "ok"),
                  key=lambda r: (-r.prompt_len, r.rid))
    if not done:
        return []
    rest = done[1:]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(pick)]


def gaps(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per row: (best logit - served token's logit) / row std."""
    rows = np.arange(len(tokens))
    return (logits.max(-1) - logits[rows, tokens]) / logits.std(-1)


def readings(weights, arch: dict, max_len: int, max_new: int, prompts,
             picked, precision: str = "float32",
             ref=reference) -> Dict[str, float]:
    """The compared numbers over every served token of ``picked`` results
    (``prompts``: rid -> prompt; ``max_new``: the mix's longest output),
    against the reference module ``ref``.
    With ``precision`` other than float32 the token compared at each
    position is the one that precision's reference puts first, over the
    same prompts and served tokens: the reading of the lower-precision
    control."""
    g = []
    for r in picked:
        tok = np.asarray(r.tokens, np.int64)
        want = ref.served_logits(weights, arch, max_len, prompts[r.rid], tok,
                                 max_new)
        if precision != "float32":
            tok = ref.served_logits(weights, arch, max_len, prompts[r.rid],
                                    tok, max_new,
                                    precision=precision).argmax(-1)
        g.append(gaps(want, tok))
    g = np.concatenate(g)
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
            "requests": len(picked), "tokens": int(g.size)}


def judge(picked, vocab: int, numbers: Dict[str, float],
          limits: Dict[str, float]) -> bool:
    """True when every compared request is ok with ``n_new``
    in-vocabulary tokens and every compared number is within its limit."""
    if not picked:
        return False
    for r in picked:
        t = np.asarray(r.tokens)
        if r.status != "ok" or len(t) != r.n_new or not (
                (t >= 0) & (t < vocab)).all():
            return False
    return all(numbers[k] <= limits[k] for k in limits)
