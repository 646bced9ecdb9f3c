"""The benchmark's operation and byte counts against hand counts at the
tiny test sizes (bench_tiny): every term written out. The per-block
counts come from each configuration's reference; flops.py composes them."""
import bench_tiny as T
import pytest

from harness import flops

Q, M, E = T.TINY_QWEN, T.TINY_MAMBA, T.TINY_MOE
QR, MR, ER = T.reference(Q), T.reference(M), T.reference(E)
# tiny_qwen: d 64, 4 heads of 16, 2 KV heads, FFN 128, 2 layers, vocabulary 512
Q_MATMUL = 64 * 16 * (4 + 2 * 2) + 4 * 16 * 64 + 3 * 64 * 128       # 36,864
Q_BLOCK = Q_MATMUL + 16 * (4 + 2 * 2) + 2 * 64                      # + biases, norms
# tiny_mamba: d 64, d_inner 128, state 16, head dim 16 (8 heads), conv 4
M_MATMUL = 64 * (2 * 128 + 2 * 16 + 8) + 128 * 64                   # 27,136
M_BLOCK = M_MATMUL + 5 * (128 + 32) + 3 * 8 + 128 + 64              # conv w+b, A/D/dt, gnorm, ln
# tiny_moe: d 64, 4 heads of 16, 4 KV heads; block 0 dense (FFN 256), blocks
# 1-2 MoE: router to 8 experts of width 32, top 2, 2 shared experts
E_ATTN = 64 * 16 * (4 + 2 * 4) + 4 * 16 * 64                        # 16,384
E_DENSE = E_ATTN + 3 * 64 * 256                                     # 65,536
E_MOE = E_ATTN + 64 * 8 + 3 * 64 * 32 * (2 + 2)                     # 41,472 a token passes
E_MOE_ALL = E_ATTN + 64 * 8 + 3 * 64 * 32 * (8 + 2)                 # 78,336 held


def test_block_params():
    assert QR.block_matmul_params(Q, 0) == 36864 == Q_MATMUL
    assert QR.block_params(Q, 0) == Q_BLOCK == 37120
    assert MR.block_matmul_params(M, 0) == 27136 == M_MATMUL
    assert MR.block_params(M, 0) == M_BLOCK == 28152


def test_moe_block_params():
    assert [ER.block_matmul_params(E, i) for i in range(3)] == [E_DENSE, E_MOE, E_MOE]
    assert [ER.block_params(E, i) for i in range(3)] == \
        [E_DENSE + 2 * 64, E_MOE_ALL + 2 * 64, E_MOE_ALL + 2 * 64]


@pytest.mark.parametrize("ref,conf,i,seq,want", [
    (QR, Q, 0, 32, 2 * 36864 + 2 * 2 * 16 * 4 * (32 + 1) / 2),     # matmuls + QK^T, PV
    (MR, M, 0, 32, 2 * 27136 + 2 * 4 * 160 + 2 * 2 * 8 * 16 * 16),  # matmuls + conv + recurrence
    (ER, E, 0, 32, 2 * E_DENSE + 2 * 2 * 16 * 4 * (32 + 1) / 2),    # dense block
    (ER, E, 2, 32, 2 * E_MOE + 2 * 2 * 16 * 4 * (32 + 1) / 2),      # k routed + shared experts
], ids=["qwen", "mamba", "moe-dense", "moe-experts"])
def test_forward_per_token(ref, conf, i, seq, want):
    assert ref.block_fwd_flops_per_token(conf, i, seq) == want


def test_walk_block():
    # 3 epochs of fwd+bwd (3 passes each) and two stream advances, 512 tokens
    assert flops.walk_block_flops(QR, Q, 0, 32, 512, 3) == (3 * 3 + 2) * 77952 * 512
    # block 1 of tiny_moe, 2 epochs run: its own forward, not block 0's
    fwd = 2 * E_MOE + 2 * 2 * 16 * 4 * (32 + 1) / 2
    assert flops.walk_block_flops(ER, E, 1, 32, 512, 2) == (3 * 2 + 2) * fwd * 512


def test_serve_request():
    # prompt 8, 5 generated: 12 tokens pass 2 blocks, the head scores 5
    assert flops.serve_request_flops(QR, Q, 8, 5) == 2 * 2 * 36864 * 12 + 2 * 64 * 512 * 5
    # tiny_moe: the dense block and two MoE blocks, each as a token passes it
    assert flops.serve_request_flops(ER, E, 8, 5) == \
        2 * (E_DENSE + 2 * E_MOE) * 12 + 2 * 64 * 512 * 5


def test_decode_bytes():
    weights = 4 * (2 * Q_BLOCK + 64 + 64 * 512 + 64)  # blocks, final norm, head, one row
    assert flops.decode_weight_bytes(QR, Q) == weights == 428544
    kv_per_position = 4 * 2 * 2 * 2 * 16              # f32, K and V, 2 layers, 2 heads of 16
    # prompt 8, 5 generated: 4 decode steps attend to 9, 10, 11, 12 positions
    assert flops.decode_request_bytes(QR, Q, 8, 5) == 4 * weights + kv_per_position * 42
    with pytest.raises(ValueError):                   # a Mamba2 block keeps no K/V
        flops.decode_kv_bytes(MR, M, 42)


def test_walk_mfu_counts_each_block_by_its_index():
    import types

    from harness import spec as S

    reader = S.load_module(f"{T.BENCH}/metrics/walk_mfu.py", "walk_mfu_reader")
    run = types.SimpleNamespace(
        ref=ER, conf=E, flops=flops, chips=1, peaks={"flops": 1e12},
        trace=types.SimpleNamespace(window_s=2.0),
        counts={"seq_len": 32, "tokens": 512, "epochs_run": [(0, 3), (1, 2), (2, 1)]})
    work = sum(flops.walk_block_flops(ER, E, i, 32, 512, e) for i, e in [(0, 3), (1, 2), (2, 1)])
    assert reader.read(run) == 100.0 * work / 2e12
    assert work != sum(flops.walk_block_flops(ER, E, 0, 32, 512, e) for e in (3, 2, 1))
