"""Inputs come from the seed alone, and every seed gets the same sizes."""
import bench_tiny as T  # noqa: F401
import numpy as np

from harness import traffic

BIG = 2**31 + 12345  # the driver's seeds exceed 32 signed bits


def test_same_seed_same_inputs():
    a = traffic.calibration_sets(T.TINY_WALK, 512, BIG, 2)
    b = traffic.calibration_sets(T.TINY_WALK, 512, BIG, 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].shape == (16, 32) and a[0].dtype == np.int32
    assert 0 <= a[0].min() and a[0].max() < 512
    assert not np.array_equal(a[0], a[1])
    c = traffic.calibration_sets(T.TINY_WALK, 512, BIG + 1, 1)
    assert not np.array_equal(a[0], c[0])


def test_seeds_share_the_sizes():
    cyc = T.TINY_SERVE["cycle"]
    sizes = []
    for seed in (1, BIG):
        reqs = traffic.requests(T.TINY_SERVE, 512, seed, 2)
        assert len(reqs) == 2 * sum(cyc["prompt_counts"])
        sizes.append(sorted((len(p), n) for p, n in reqs))
        for p, n in reqs:
            assert len(p) in cyc["prompt_lengths"]
            assert cyc["output"]["min"] <= n <= cyc["output"]["max"]
            assert len(p) + n <= T.TINY_SERVE["max_len"]
    assert sorted(p for p, _ in sizes[0]) == sorted(p for p, _ in sizes[1])
    assert sorted(n for _, n in sizes[0]) == sorted(n for _, n in sizes[1])


def test_output_lengths_are_quantiles():
    spec = {"median": 64, "sigma": 0.8, "min": 16, "max": 256}
    outs = traffic.output_lengths(spec, 20)
    assert outs == sorted(outs) and outs[0] == 16 and outs[-1] == 256
    assert outs[9] <= 64 <= outs[10]


def test_jax_key_takes_big_seeds():
    import jax

    k1, k2 = traffic.jax_key(BIG), traffic.jax_key(BIG + 1)
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
