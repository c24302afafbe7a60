"""Work counts against hand counts at a tiny configuration, and the peak
table."""
import pytest

from bench import reference, work

ARCH = dict(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, vocab=512, rope_theta=1e4, norm_eps=1e-5,
            dtype="bfloat16", param_dtype="bfloat16",
            dsa=dict(sparsity=0.9, sigma=0.25, quant_bits=4, block_q=16,
                     block_k=16, min_blocks=1, local_blocks=1,
                     decode_local=64))
MAX_LEN = 512
# one layer's matmul weights: wq 64x64, wk/wv 64x32, wo 64x64, DSA p 64x16
# and wq~/wk~ 16x16, MLP w1/w3 64x128 and w2 128x64
LAYER = 4096 + 2048 + 2048 + 4096 + 1024 + 256 + 256 + 3 * 8192


def geo(plen=1):
    return reference.geometry(ARCH, MAX_LEN, plen)


def test_bench_work_weights_and_token():
    assert work._matmul_params(ARCH) == 2 * LAYER
    assert work.attn_layers(ARCH) == 2
    assert work.token_flops(ARCH) == 2 * (2 * LAYER + 64 * 512)
    # layers (+ two norms each), the head, the final norm; 2 bytes each
    assert work.weight_bytes(ARCH) == 2 * (2 * (LAYER + 128) + 64 * 512 + 64)


def test_bench_work_decode_geometry():
    g = geo()
    # keep = round(512 * 0.1) = 51 rows -> 4 blocks, + 64-row window (4
    # blocks) + 1
    assert g["nb_keep_dec"] == 9 and g["n_kb_dec"] == 32
    assert work.decode_kept_rows(g, 100) == 100        # 7 blocks: all kept
    assert work.decode_kept_rows(g, 200) == 8 * 16 + 8  # 8 whole + 8 rows


def test_bench_work_decode_kernel():
    w = work.decode_kernel(ARCH, geo(), [100, 200])
    rows = 100 + 136
    assert w.flops == 2 * (4 * 4 * 16 * rows)
    assert w.bytes == 2 * (rows * 2 * 2 * 16 * 2 + 2 * 2 * 4 * 16 * 2)


def test_bench_work_chunk_kernel():
    # a 40-row prompt in the 64 bucket keeps 2 blocks per query block:
    # rows 0-15 see 1..16 keys, rows 16-31 16 + 1..16, rows 32-39 16 + 1..8
    g = geo(40)
    assert g["bucket"] == 64 and g["nb_keep_pre"] == 2
    keys = 136 + (16 * 16 + 136) + (8 * 16 + 36)
    w = work.chunk_kernel(ARCH, geo, [40])
    assert w.flops == 2 * (4 * 4 * 16 * keys)
    # 1 + 2 + 2 kept blocks of 16 K and V rows; q in and out of 40 rows
    assert w.bytes == 2 * (5 * 16 * 2 * 2 * 16 * 2 + 40 * 2 * 4 * 16 * 2)


def test_bench_work_prefill_adds_matmuls_and_weights():
    w = work.prefill(ARCH, geo, [40])
    k = work.chunk_kernel(ARCH, geo, [40])
    assert w.flops > k.flops + 40 * 2 * 2 * LAYER
    assert w.bytes > k.bytes + work.weight_bytes(ARCH)


def test_bench_work_least_time_and_bound():
    p = work.peak("TPU v5 lite")
    assert p == {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                 "hbm_bytes": 16e9}
    w = work.Work(flops=197e12, bytes=819e9 * 2)
    assert w.least_s(p) == 2.0 and w.bound(p) == "memory"


def test_bench_work_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")
