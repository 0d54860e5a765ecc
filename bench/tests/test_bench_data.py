"""The benchmark's generators: deterministic from the seed, the same
sizes for every seed, and the shares and counts the configurations
state."""

import numpy as np
import pytest

from bench.harness import data, traffic

TAO = {"read_frac": 0.998,
       "reads": {"get_edges": 0.594, "count_edges": 0.117,
                 "get_node": 0.289},
       "writes": {"create_edge": 0.8, "delete_edge": 0.2}}


def test_tao_block_composition():
    counts = traffic.composition(traffic.shares(TAO), 5000)
    assert counts == {"get_edges": 2964, "count_edges": 584,
                      "get_node": 1442, "create_edge": 8, "delete_edge": 2}


@pytest.mark.parametrize("block", [1, 7, 1000, 5000])
def test_composition_sums_to_block(block):
    assert sum(traffic.composition(traffic.shares(TAO), block).values()) \
        == block


def test_stream_deterministic_and_exact_per_block():
    zipf = data.Zipf(1000, 0.99, np.arange(1000))

    def take(seed, n):
        s = traffic.stream(traffic.shares(TAO), 5000,
                           np.random.default_rng(seed), zipf.draw)
        return [next(s) for _ in range(n)]
    a, b, c = take(1, 10000), take(1, 10000), take(2, 10000)
    assert a == b and a != c
    for reqs in (a, c):
        for blk in (reqs[:5000], reqs[5000:]):
            kinds = [r.kind for r in blk]
            assert kinds.count("create_edge") == 8
            assert kinds.count("delete_edge") == 2


def test_zipf_skew():
    zipf = data.Zipf(10000, 0.99, np.arange(10000)[::-1])
    v = zipf.draw(np.random.default_rng(0), 200000)
    top = np.mean(v == 9999)      # rank 1 is vertex 9999 here
    h = np.sum(1.0 / np.arange(1, 10001) ** 0.99)
    assert abs(top - 1.0 / h) < 0.01


def test_power_law_sizes_and_seed():
    a = data.power_law_edges(np.random.default_rng(5), 2000, 14.2, 0.8)
    b = data.power_law_edges(np.random.default_rng(5), 2000, 14.2, 0.8)
    c = data.power_law_edges(np.random.default_rng(6), 2000, 14.2, 0.8)
    for s, d in (a, c):
        assert s.size == d.size == 28400
        assert not np.any(s == d)
        assert s.min() >= 0 and max(s.max(), d.max()) < 2000
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_rmat_distinct_undirected_fixed_count(seed):
    lo, hi, perm = data.rmat_edges(np.random.default_rng(seed), 10, 16,
                                   (0.57, 0.19, 0.19, 0.05), 12000)
    assert lo.size == hi.size == 12000
    assert np.all(lo < hi) and hi.max() < 1024
    assert np.unique(lo * 1024 + hi).size == 12000
    assert sorted(perm.tolist()) == list(range(1024))
    lo2, hi2, _ = data.rmat_edges(np.random.default_rng(seed), 10, 16,
                                  (0.57, 0.19, 0.19, 0.05), 12000)
    assert np.array_equal(lo, lo2) and np.array_equal(hi, hi2)


def test_rmat_quadrant_shares():
    s, d = data.rmat_pairs(np.random.default_rng(0), 1, 400000,
                           (0.57, 0.19, 0.19, 0.05), np.arange(2))
    q = np.bincount(s * 2 + d, minlength=4) / s.size
    assert np.allclose(q, [0.57, 0.19, 0.19, 0.05], atol=0.005)


def test_rmat_draws_more_when_short():
    # scale 6 holds 2016 distinct pairs; one draw of 1024 edges cannot
    # give 1500 distinct ones, so the generator draws again
    lo, hi, _ = data.rmat_edges(np.random.default_rng(1), 6, 16,
                                (0.25, 0.25, 0.25, 0.25), 1500)
    assert np.unique(lo * 64 + hi).size == 1500
