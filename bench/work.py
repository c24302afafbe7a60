"""Operations and bytes the served model needs, and the chip's peaks.

Every count comes from the configuration, the program's weight layout
(``weights.layout``: which weights a token passes through) and the
contexts the window served, never from how the program happens to
compute them, so removing wasted work raises a share and a roofline share
cannot pass 100% unless a count or a time is wrong.  DSA counts take the
blocks the configuration keeps (``reference.geometry``): a decode step at
context c reads the real rows of ``nb_keep_dec`` blocks, a prompt query
block the real rows of ``nb_keep_pre`` blocks at or before it.  Bytes are
HBM traffic: each weight read once per decode step (once per prompt in
prefill), each kept K/V row read once, each new K/V/K~ row written once.
A routed expert's weights (a leaf with an ``"expert"`` axis) count at
``top_k`` of ``num_experts`` in FLOPs, which only the routed experts
need, and whole in bytes, which a step reads; attention and DSA counts
take the layers that hold a DSA predictor.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable

import numpy as np

from bench.weights import layout, predictor_k

HERE = Path(__file__).resolve().parent


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def least_s(self, peak: dict) -> float:
        """The least time the chip could take: the larger of the two bounds."""
        return max(self.flops / peak["flops_bf16"],
                   self.bytes / peak["hbm_bytes_per_s"])

    def bound(self, peak: dict) -> str:
        return ("compute" if self.flops / peak["flops_bf16"]
                >= self.bytes / peak["hbm_bytes_per_s"] else "memory")


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    kinds = json.loads((HERE / "peaks.json").read_text())["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(kinds)}")
    return kinds[device_kind]


def _itemsize(arch: dict) -> int:
    return int(np.dtype(arch["dtype"]).itemsize)


def _matmul_params(arch: dict) -> int:
    """Matmul weights one token goes through in all layers (attention, DSA
    predictor, MLP or experts): every layer leaf but the norms and the
    biases, a routed expert leaf at top_k of num_experts."""
    n = 0
    for path, leaf in layout(arch).items():
        if (not path.startswith(("groups/", "prologue/"))
                or "norm" in path.rsplit("/", 1)[-1] or len(leaf.core) < 2):
            continue
        if "expert" in leaf.axes:
            n += leaf.size // arch["moe"]["num_experts"] * arch["moe"]["top_k"]
        else:
            n += leaf.size
    return n


def attn_layers(arch: dict) -> int:
    """Layers that hold a DSA predictor (and so a K/V and K~ cache)."""
    return sum(leaf.shape[0] if leaf.stacked else 1
               for path, leaf in layout(arch).items()
               if path.endswith("attn/dsa/p"))


def weight_bytes(arch: dict) -> float:
    """Weights a full forward pass reads: every leaf but the embedding
    table (a lookup reads a row), each expert whole."""
    return float(sum(leaf.size * np.dtype(leaf.dtype).itemsize
                     for path, leaf in layout(arch).items()
                     if path != "embed"))


def token_flops(arch: dict) -> float:
    """Matmul FLOPs of one token through every layer and the head."""
    return 2.0 * (_matmul_params(arch) + arch["d_model"] * arch["vocab"])


def decode_kv_lens(results) -> list:
    """kv_len of every decode step's token in ``results``: its prompt and
    the tokens before it (token 0 comes from prefill)."""
    return [r.prompt_len + i for r in results
            for i in range(1, len(r.tokens))]


def decode_kept_rows(geo: dict, kv_len: int) -> int:
    """Real cache rows a decode step at ``kv_len`` rows attends."""
    bk = geo["block_k"]
    if -(-kv_len // bk) <= geo["nb_keep_dec"]:
        return kv_len
    return (geo["nb_keep_dec"] - 1) * bk + (kv_len - 1) % bk + 1


def decode_kernel(arch: dict, geo: dict, kv_lens: Iterable[int]) -> Work:
    """The DSA decode kernel over every layer for decode tokens at the
    given ``kv_len``s: q.k and p.v over the kept rows, reading the kept
    K/V rows and q, writing the output."""
    hq, hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    b = _itemsize(arch)
    rows = np.array([decode_kept_rows(geo, c) for c in kv_lens], np.float64)
    per_layer = Work(4.0 * hq * hd * rows.sum(),
                     rows.sum() * 2 * hkv * hd * b
                     + len(rows) * 2 * hq * hd * b)
    n = attn_layers(arch)
    return Work(per_layer.flops * n, per_layer.bytes * n)


def decode_steps(arch: dict, geo: dict, kv_lens: Iterable[int],
                 steps: int) -> Work:
    """Whole decode steps: every weight read once per step, the matmuls of
    each decode token, the kernel's work, each token's K~ block scores over
    the valid blocks, and the one-row K/V/K~ writes and block-sum update."""
    kv_lens = list(kv_lens)
    hkv, hd = arch["n_kv_heads"], arch["head_dim"]
    k = predictor_k(arch["d_model"], arch["dsa"]["sigma"])
    b = _itemsize(arch)
    bk = geo["block_k"]
    blocks = np.array([-(-c // bk) for c in kv_lens], np.float64).sum()
    n = len(kv_lens)
    per_layer = Work(2.0 * k * blocks,
                     blocks * k * b + n * (2 * hkv * hd + 3 * k) * b)
    w = decode_kernel(arch, geo, kv_lens)
    layers = attn_layers(arch)
    return Work(token_flops(arch) * n + w.flops + per_layer.flops * layers,
                weight_bytes(arch) * steps + w.bytes
                + per_layer.bytes * layers)


def _prompt_rows(geo: dict, plen: int):
    """Per real prompt row: kept key rows, and per query block: kept
    blocks (the prompt's block selection, causal within the diagonal)."""
    bq, bk, nb = geo["block_q"], geo["block_k"], geo["nb_keep_pre"]
    i = np.arange(plen)
    qb = i // bq
    keys = (np.minimum(nb, qb * bq // bk + 1) - 1) * bk + i % bk + 1
    n_qb = -(-plen // bq)
    blocks = np.minimum(nb, np.arange(n_qb) * bq // bk + 1)
    return keys.astype(np.float64), blocks.astype(np.float64)


def chunk_kernel(arch: dict, geo_of, prompt_lens: Iterable[int]) -> Work:
    """The DSA chunk-prefill kernel over every layer for the given prompts:
    each real query row against the real rows of its block's kept blocks
    (causal), reading each query block's kept K/V blocks once, q and the
    output once.  ``geo_of(plen)`` gives a prompt's geometry."""
    hq, hkv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    b = _itemsize(arch)
    w = Work()
    for plen in prompt_lens:
        geo = geo_of(plen)
        keys, blocks = _prompt_rows(geo, plen)
        w = w + Work(4.0 * hq * hd * keys.sum(),
                     blocks.sum() * geo["block_k"] * 2 * hkv * hd * b
                     + plen * 2 * hq * hd * b)
    n = attn_layers(arch)
    return Work(w.flops * n, w.bytes * n)


def prefill(arch: dict, geo_of, prompt_lens: Iterable[int]) -> Work:
    """Whole prompt ingestion: the layer matmuls of every real prompt token
    and the head's for its last, the
    chunk kernel's work, the K~ block selection of every query block, the
    K/V/K~ rows written once, and the weights read once per prompt."""
    prompt_lens = list(prompt_lens)
    hkv, hd = arch["n_kv_heads"], arch["head_dim"]
    k = predictor_k(arch["d_model"], arch["dsa"]["sigma"])
    b = _itemsize(arch)
    w = chunk_kernel(arch, geo_of, prompt_lens)
    sel = Work()
    for plen in prompt_lens:
        bq = geo_of(plen)["block_q"]
        n_qb = -(-plen // bq)
        # each query block scores every key row at or before it
        sel = sel + Work(2.0 * k * sum(min(plen, (j + 1) * bq)
                                       for j in range(n_qb)),
                         plen * (2 * hkv * hd + k) * b)
    # every prompt row through every layer; the head only for the last row
    n_tok = sum(prompt_lens)
    head = 2.0 * arch["d_model"] * arch["vocab"]
    layers = attn_layers(arch)
    return Work((token_flops(arch) - head) * n_tok + head * len(prompt_lens)
                + w.flops + sel.flops * layers,
                weight_bytes(arch) * len(prompt_lens) + w.bytes
                + sel.bytes * layers)
