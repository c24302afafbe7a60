"""The serving kernels compile for a TPU v5e at published model widths.

Nothing runs: each test lowers a Pallas kernel for one chip of a DESCRIBED
``v5e:2x2`` topology and hands it to the TPU compiler, which refuses what
the chip would refuse (block shapes off the (8, 128) tiling, more scoped
VMEM than a kernel may use).  Interpret-mode tests cannot see either, so
these guard every kernel of the serving path at no chip time.  Widths are
those of ``stablelm_3b`` (MHA, 32 heads of 80) and ``yi_6b`` (GQA 32/4,
head 128) at their 128x128 DSA blocks, with bf16, float32, int8 and fp8
caches.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

ARCHS = ("stablelm_3b", "yi_6b")
B, S, NB, C = 4, 2048, 8, 256        # slots, cache rows, selected blocks, chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _dims(arch):
    cfg = get_config(arch)
    return (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dsa.block_q,
            cfg.dsa.block_k)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kv(hkv, hd, rows, quant):
    """K/V (+ per-row scale) shapes of a dense (rows=(B, S)) cache or a
    page pool (rows=(P*block_k,))."""
    dt = {None: jnp.bfloat16, "f32": jnp.float32, "int8": jnp.int8,
          "fp8": jnp.float8_e4m3fn}[quant]
    kv = [(rows + (hkv, hd), dt)] * 2
    scaled = quant in ("int8", "fp8")
    return kv + ([(rows + (hkv,), jnp.float32)] * 2 if scaled else [])


@pytest.mark.parametrize("quant", [None, "f32", "int8", "fp8"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("arch", ARCHS)
def test_dsa_decode_kernel_compiles(one_chip, arch, paged, quant):
    hq, hkv, hd, _, bk = _dims(arch)
    i32 = jnp.int32
    if paged:
        kv = _kv(hkv, hd, (B * S + bk,), quant)   # + the zero page
        shapes = [((B, 1, hq, hd), jnp.bfloat16), ((B, NB), i32),
                  ((B, NB), i32), ((B, NB), i32), ((B,), i32)]

        def fn(q, idx, pidx, ok, kvl, k, v, *sc):
            return ops.dsa_decode_paged(
                q, k, v, idx, pidx, ok, kvl, block_k=bk, interpret=False,
                **dict(zip(("k_scale", "v_scale"), sc)))
    else:
        kv = _kv(hkv, hd, (B, S), quant)
        shapes = [((B, 1, hq, hd), jnp.bfloat16), ((B, NB), i32),
                  ((B, NB), i32), ((B,), i32)]

        def fn(q, idx, ok, kvl, k, v, *sc):
            return ops.dsa_decode(
                q, k, v, idx, ok, kvl, block_k=bk, interpret=False,
                **dict(zip(("k_scale", "v_scale"), sc)))
    _compile(fn, one_chip, *shapes, *kv)


@pytest.mark.parametrize("quant", [None, "f32", "int8", "fp8"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("arch", ARCHS)
def test_dsa_chunk_kernel_compiles(one_chip, arch, paged, quant):
    hq, hkv, hd, bq, bk = _dims(arch)
    i32 = jnp.int32
    sel = (B, C // bq, NB)
    if paged:
        kv = _kv(hkv, hd, (B * S + bk,), quant)
        shapes = [((B, C, hq, hd), jnp.bfloat16), (sel, i32), (sel, i32),
                  (sel, i32), ((B,), i32), ((B,), i32)]

        def fn(q, idx, pidx, ok, qoff, kvl, k, v, *sc):
            return ops.dsa_chunk_prefill_paged(
                q, k, v, idx, pidx, ok, qoff, kvl, block_q=bq, block_k=bk,
                interpret=False, **dict(zip(("k_scale", "v_scale"), sc)))
    else:
        kv = _kv(hkv, hd, (B, S), quant)
        shapes = [((B, C, hq, hd), jnp.bfloat16), (sel, i32), (sel, i32),
                  ((B,), i32), ((B,), i32)]

        def fn(q, idx, ok, qoff, kvl, k, v, *sc):
            return ops.dsa_chunk_prefill(
                q, k, v, idx, ok, qoff, kvl, block_q=bq, block_k=bk,
                interpret=False, **dict(zip(("k_scale", "v_scale"), sc)))
    _compile(fn, one_chip, *shapes, *kv)


@pytest.mark.parametrize("arch", ARCHS)
def test_dsa_attention_kernel_compiles(one_chip, arch):
    hq, hkv, hd, bq, bk = _dims(arch)
    sel = (B, S // bq, NB)
    fn = functools.partial(ops.dsa_attention, block_q=bq, block_k=bk,
                           interpret=False)
    _compile(fn, one_chip, ((B, S, hq, hd), jnp.bfloat16),
             ((B, S, hkv, hd), jnp.bfloat16), ((B, S, hkv, hd), jnp.bfloat16),
             (sel, jnp.int32), (sel, jnp.int32))
