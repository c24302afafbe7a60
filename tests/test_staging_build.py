"""The compiled cache builder (``ContinuousEngine._new_cache``): one jitted
program per geometry builds the same tree the eager
``unstack_group_caches(init_cache(...))`` gives — tree structure, leaf
shapes, dtypes and all-zero values — for dense, paged-DSA and int8-KV
caches at admission widths 1 and ``slots``; ``stats["staging_builds"]``
counts one build per chunked admission group.  (The mesh case, each leaf's
sharding, is in tests/test_multidevice.py.)"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.inference.scheduler import ContinuousEngine, Request
from repro.inference.telemetry import Telemetry
from repro.models.transformer import init_cache, init_model, \
    unstack_group_caches

MAX_LEN = 96
SLOTS = 2
BUCKET = 64

VARIANTS = {
    "dense": ("stablelm_3b", {}),
    "paged_dsa": ("yi_6b", dict(long_context=True, dsa_mode="block",
                                paged=True)),
    "int8": ("stablelm_3b", dict(kv_quant="int8")),
}


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def engine(request, rng):
    arch, kw = VARIANTS[request.param]
    cfg = reduced(get_config(arch))
    params, _ = init_model(rng, cfg)
    return request.param, ContinuousEngine(cfg, params, slots=SLOTS,
                                           max_len=MAX_LEN, seg_len=4, **kw)


def _eager(ce, batch, rows, pages=None):
    return unstack_group_caches(init_cache(
        ce.cfg, batch, rows, ce.engine.decode_flags,
        dtype=ce.engine.cache_dtype, pages=pages))


@pytest.mark.parametrize("width", [1, SLOTS])
def test_builder_matches_eager_tree(engine, width):
    """Staging geometry (dense rows of the prompt bucket) for every
    variant; the paged variant also builds its page pool (``pages=``)."""
    name, ce = engine
    geoms = [(width, BUCKET, None)]
    if name == "paged_dsa":
        geoms.append((width, MAX_LEN, ce.pool_pages))
    for batch, rows, pages in geoms:
        got = ce._new_cache(batch, rows, pages)
        want = _eager(ce, batch, rows, pages)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        paths = jax.tree_util.tree_flatten_with_path(want)[0]
        for (path, w), g in zip(paths, jax.tree.leaves(got)):
            key = jax.tree_util.keystr(path)
            assert g.shape == w.shape, key
            assert g.dtype == w.dtype, key
            assert not np.asarray(g).any(), key
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=key)
        if name == "int8":
            assert any(jax.tree_util.keystr(p).endswith("['k_s']")
                       for p, _ in paths)


def test_builder_compiles_once_per_geometry(engine):
    """Builders are cached per static (batch, rows, pages) key: a second
    build of a geometry reuses its program and returns fresh buffers."""
    _, ce = engine
    a = ce._new_cache(1, BUCKET)
    n = len(ce._builders)
    b = ce._new_cache(1, BUCKET)
    assert len(ce._builders) == n
    la, lb = jax.tree.leaves(a)[0], jax.tree.leaves(b)[0]
    assert la.unsafe_buffer_pointer() != lb.unsafe_buffer_pointer()


def test_staging_builds_count_chunked_groups(rng):
    """One staging build per chunked admission group: the counter equals
    the ``serve.admit.staging`` spans of chunked groups on the trace
    ring, and tokens are those of an engine without telemetry."""
    cfg = reduced(get_config("stablelm_3b"))
    params, _ = init_model(rng, cfg)
    tel = Telemetry(sample_every=0)
    kw = dict(slots=SLOTS, max_len=MAX_LEN, seg_len=4)
    ce = ContinuousEngine(cfg, params, telemetry=tel, **kw)
    plain = ContinuousEngine(cfg, params, **kw)
    rs = np.random.default_rng(5)

    def mk():
        return [Request(rid, rs.integers(1, cfg.vocab - 4, size=(l,))
                        .astype(np.int32), n, greedy=True, seed=rid)
                for rid, (l, n) in enumerate(
                    [(20, 5), (40, 6), (25, 3), (33, 8), (18, 2), (50, 1)])]

    got = ce.run(mk())
    rs = np.random.default_rng(5)
    exp = plain.run(mk())
    groups = [e for e in tel.events if e["name"] == "serve.admit.staging"
              and e["args"].get("kind") == "chunked"]
    assert ce.chunked and len(groups) > 0
    assert ce.stats["staging_builds"] == len(groups)
    assert plain.stats["staging_builds"] == len(groups)
    for rid in exp:
        np.testing.assert_array_equal(got[rid], exp[rid], err_msg=str(rid))
