"""The model step's stages are named in its compiled programs.

Every stage of ``decode_step`` and ``chunk_step`` runs under one
``jax.named_scope`` (``STAGES``; ``weights`` is the per-layer slicing of
the stacked parameters), so each instruction of the compiled decode
segment and chunk program carries its stage in the ``op_name`` of its
metadata, where a profiler trace's operations can be matched to it.
"""
import re

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.scheduler import ContinuousEngine, Request
from repro.models.transformer import init_model

STAGES = ("weights", "qkv", "kv_write", "dsa_predict", "dsa_select",
          "attend", "mlp", "logits_sample")
MAX_LEN = 96


def _stages(hlo: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', hlo)
    return {part for n in names for part in n.split("/")} & set(STAGES)


@pytest.fixture(scope="module")
def engine(rng):
    cfg = reduced(get_config("yi_6b"))
    params, _ = init_model(rng, cfg)
    ce = ContinuousEngine(cfg, params, slots=2, max_len=MAX_LEN, seg_len=4,
                          long_context=True, dsa_mode="kernel", paged=True)
    return cfg, ce


@pytest.mark.parametrize("program", ["segment", "chunk_1", "chunk_slots"])
def test_compiled_programs_name_every_stage(engine, program):
    cfg, ce = engine
    if program == "segment":
        hlo = ce.segment_hlo()
    else:
        hlo = ce.chunk_hlo(64, 1 if program == "chunk_1" else None)
    assert _stages(hlo) == set(STAGES), sorted(_stages(hlo))
    # the Pallas kernel runs under the attend stage
    assert re.search(r'op_name="[^"]*/attend/[^"]*dsa_(decode|chunk)', hlo)


def test_chunk_hlo_is_the_served_program(engine):
    """``chunk_hlo`` compiles the chunk program with the shapes chunked
    admission dispatches: its text is the served program's."""
    cfg, ce = engine
    seen = []
    orig = ce._chunk

    def capture(*a, **k):
        seen.append((a, k))
        return orig(*a, **k)

    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(1, cfg.vocab - 4, size=(40,)).astype(
        np.int32), 3) for i in range(2)]
    ce._chunk = capture
    try:
        ce.run(reqs)
    finally:
        ce._chunk = orig
    a, k = seen[0]
    assert a[2].shape[0] == ce.slots         # one group of both requests
    served = orig.lower(*a, **k).compile().as_text()
    assert ce.chunk_hlo(40) == served
