"""The plain reference: strict-serializability windows for reads, and the
analytics references against straightforward loops."""

import numpy as np
import pytest

from bench.harness import reference as ref


def _base():
    a = ref.Adjacency(["a", "b"])
    a.add("a", 1, "b")
    a.add("a", 2, "b")
    return a


def test_write_acked_before_issue_must_be_visible():
    w = ref.Write("create_edge", "a", 3, "b", t_issue=1.0, t_ack=2.0,
                  ok=True)
    seen = ref.Read("get_edges", "a", 3.0, 4.0, [(1, "b"), (2, "b"),
                                                (3, "b")])
    missed = ref.Read("count_edges", "a", 3.0, 4.0, 2)
    n, bad, _ = ref.check_reads(_base(), [w], [seen, missed])
    assert (n, bad) == (2, 1)


def test_concurrent_write_may_or_may_not_be_visible():
    w = ref.Write("delete_edge", "a", 1, None, t_issue=1.0, t_ack=5.0,
                  ok=True)
    before = ref.Read("get_node", "a", 2.0, 3.0, {"id": "a", "n_edges": 2})
    after = ref.Read("get_node", "a", 2.0, 3.0, {"id": "a", "n_edges": 1})
    n, bad, _ = ref.check_reads(_base(), [w], [before, after])
    assert (n, bad) == (2, 0)


def test_write_issued_after_read_must_not_be_visible():
    w = ref.Write("create_edge", "a", 9, "a", t_issue=5.0, t_ack=6.0,
                  ok=True)
    r = ref.Read("count_edges", "a", 1.0, 2.0, 3)
    assert ref.check_reads(_base(), [w], [r])[1] == 1


def test_aborted_write_is_never_visible():
    w = ref.Write("create_edge", "a", 9, "a", t_issue=1.0, t_ack=1.5,
                  ok=False)
    assert ref.check_reads(_base(), [w], [
        ref.Read("count_edges", "a", 2.0, 3.0, 3)])[1] == 1
    assert ref.check_reads(_base(), [w], [
        ref.Read("count_edges", "a", 2.0, 3.0, 2)])[1] == 0


def test_unfinished_read_is_not_checked():
    n, bad, _ = ref.check_reads(_base(), [], [ref.Read("get_edges", "a",
                                                       1.0)])
    assert (n, bad) == (0, 0)


def _graph(seed, n=60, m=400):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n, m), rng.integers(0, n, m), n


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pagerank_matches_a_loop(seed):
    src, dst, n = _graph(seed)
    deg = np.maximum(np.bincount(src, minlength=n), 1)
    pr = [1.0 / n] * n
    for _ in range(20):
        nxt = [0.15 / n] * n
        for s, d in zip(src.tolist(), dst.tolist()):
            nxt[d] += 0.85 * pr[s] / deg[s]
        pr = nxt
    assert np.allclose(ref.pagerank(src, dst, n, 20, 0.85), pr, rtol=1e-12)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfs_matches_a_queue(seed):
    src, dst, n = _graph(seed, m=120)
    adj = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        adj.setdefault(s, []).append(d)
    want = [-1] * n
    want[0], queue = 0, [0]
    while queue:
        v = queue.pop(0)
        for u in adj.get(v, []):
            if want[u] < 0:
                want[u] = want[v] + 1
                queue.append(u)
    assert ref.bfs_levels(src, dst, n, 0).tolist() == want


def test_edge_log_states_and_multiset_diff():
    log = ref.EdgeLog(4, np.array([0, 1]), np.array([1, 2]),
                      np.array([10, 11]))
    log.add(1, np.array([2]), np.array([3]), np.array([12]))
    log.kill(2, [10])
    keys = [ref.edge_keys(*log.at(k), 4) for k in (0, 1, 2)]
    assert [k.tolist() for k in keys] == [[1, 6], [1, 6, 11], [6, 11]]
    assert ref.multiset_diff(keys[1], keys[1]) == 0
    assert ref.multiset_diff(keys[1], keys[2]) == 1
    assert ref.multiset_diff(np.array([1, 1]), np.array([1])) == 1
