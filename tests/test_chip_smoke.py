"""CPU rehearsal of chip_smoke.py: its phases at reduced() widths, with the
Pallas kernels interpreted, and its refusal to run off the chip."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_dsa_modes_phase_reduced(smoke):
    """Both DSA modes serve every request ok through the paged continuous
    engine, and kernel vs block agree on the first decode step."""
    cfg = reduced(get_config(smoke.ARCH))
    params = smoke.build_params(cfg, seed=0)
    # one 64 bucket, a multiple of the reduced block_q: the DSA block path
    requests = smoke.make_requests(cfg, 4, (33, 60), 6, seed=0)
    serving = dict(max_len=128, slots=2, paged=True, long_context=True,
                   seg_len=4, cache_dtype=jnp.float32)
    out = smoke.dsa_modes(cfg, params, requests, serving)
    for mode in ("kernel", "block"):
        assert sorted(out[mode]["tokens"]) == [r.rid for r in requests]
        assert sorted(out[mode]["logits"]) == ["probe", "probe_prompt",
                                               "prompt", "step"]
        for lg in out[mode]["logits"].values():
            assert lg.shape == (2, cfg.vocab)
    # float32 at reduced widths: every comparison, the unchecked one too
    assert max(out["logit_err"].values()) <= 1e-4
    assert out["prefix"] == [6] * len(requests)
    assert "HloModule" in out["kernel_hlo"]


def test_kernel_twins_phase_reduced(smoke):
    """The kernel-vs-twin phase at reduced widths (MHA and GQA)."""
    for arch in ("stablelm_3b", "yi_6b"):
        cfg = reduced(get_config(arch))
        errs = smoke.kernel_twins(cfg, batch=2, seq=128, seed=0)
        assert set(errs) == {"decode", "decode_paged", "chunk", "chunk_paged"}
        assert max(errs.values()) <= smoke.KERNEL_TOL


@pytest.mark.parametrize("env", ["", "set"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """The entry points keep JAX's compile cache in $JAX_COMPILATION_CACHE_DIR
    when it is set (JAX reads it; nothing overrides it), else at the fixed
    <repo>/.jax_cache."""
    from repro.launch.serve import use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        if env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == use_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
