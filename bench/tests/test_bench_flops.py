"""The benchmark's operation and byte counts against hand counts at the
tiny test sizes (bench_tiny): every term written out."""
import bench_tiny as T  # noqa: F401  (puts bench/ on the path)
import pytest

from harness import flops

Q, M = T.TINY_QWEN, T.TINY_MAMBA
# tiny_qwen: d 64, 4 heads of 16, 2 KV heads, FFN 128, 2 layers, vocabulary 512
Q_MATMUL = 64 * 16 * (4 + 2 * 2) + 4 * 16 * 64 + 3 * 64 * 128       # 36,864
Q_BLOCK = Q_MATMUL + 16 * (4 + 2 * 2) + 2 * 64                      # + biases, norms
# tiny_mamba: d 64, d_inner 128, state 16, head dim 16 (8 heads), conv 4
M_MATMUL = 64 * (2 * 128 + 2 * 16 + 8) + 128 * 64                   # 27,136
M_BLOCK = M_MATMUL + 5 * (128 + 32) + 3 * 8 + 128 + 64              # conv w+b, A/D/dt, gnorm, ln


def test_block_params():
    assert flops.block_matmul_params(Q) == 36864 == Q_MATMUL
    assert flops.block_params(Q) == Q_BLOCK == 37120
    assert flops.block_matmul_params(M) == 27136 == M_MATMUL
    assert flops.block_params(M) == M_BLOCK == 28152


@pytest.mark.parametrize("conf,seq,want", [
    (Q, 32, 2 * 36864 + 2 * 2 * 16 * 4 * (32 + 1) / 2),     # matmuls + QK^T, PV
    (M, 32, 2 * 27136 + 2 * 4 * 160 + 2 * 2 * 8 * 16 * 16),  # matmuls + conv + recurrence
])
def test_forward_per_token(conf, seq, want):
    assert flops.block_fwd_flops_per_token(conf, seq) == want


def test_walk_block():
    # 3 epochs of fwd+bwd (3 passes each) and two stream advances, 512 tokens
    assert flops.walk_block_flops(Q, 32, 512, 3) == (3 * 3 + 2) * 77952 * 512


def test_serve_request():
    # prompt 8, 5 generated: 12 tokens pass 2 blocks, the head scores 5
    assert flops.serve_request_flops(Q, 8, 5) == 2 * 2 * 36864 * 12 + 2 * 64 * 512 * 5


def test_decode_bytes():
    weights = 4 * (2 * Q_BLOCK + 64 + 64 * 512 + 64)  # blocks, final norm, head, one row
    assert flops.decode_weight_bytes(Q) == weights == 428544
    kv_per_position = 4 * 2 * 2 * 2 * 16              # f32, K and V, 2 layers, 2 heads of 16
    # prompt 8, 5 generated: 4 decode steps attend to 9, 10, 11, 12 positions
    assert flops.decode_request_bytes(Q, 8, 5) == 4 * weights + kv_per_position * 42
