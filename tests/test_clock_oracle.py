"""Unit + property tests for refinable timestamps and the timeline oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import clock, writepath
from repro.core.clock import (Order, Stamp, compare, merge, pack, pack_many,
                              visibility_mask_np, zero)
from repro.core.oracle import (KIND_PROG, KIND_TX, CycleError, TimelineOracle)
from repro.kernels.mv_visibility import ops as mv_ops
from repro.kernels.mv_visibility.ref import before_cm


def S(epoch, clock, gk=0):
    return Stamp(epoch, tuple(clock), gk, clock[gk])


class TestVectorClock:
    def test_basic_orders(self):
        assert compare(S(0, [1, 1, 0]), S(0, [3, 4, 2])) is Order.BEFORE
        assert compare(S(0, [3, 4, 2]), S(0, [1, 1, 0])) is Order.AFTER
        # the paper's Fig. 5 concurrent pair
        assert compare(S(0, [3, 4, 2], 1), S(0, [3, 1, 5], 2)) is Order.CONCURRENT

    def test_epoch_dominates(self):
        assert compare(S(0, [100, 100]), S(1, [0, 1], 1)) is Order.BEFORE

    def test_equal(self):
        a = S(0, [2, 3], 0)
        assert compare(a, a) is Order.EQUAL

    def test_merge(self):
        assert merge((1, 5, 2), (3, 1, 2)) == (3, 5, 2)

    @given(st.lists(st.tuples(st.integers(0, 20), st.integers(0, 20),
                              st.integers(0, 20)), min_size=2, max_size=8))
    @settings(max_examples=100, deadline=None)
    def test_compare_antisymmetric_transitive(self, clocks):
        stamps = [S(0, list(c), 0) for c in clocks]
        for a in stamps:
            for b in stamps:
                oa, ob = compare(a, b), compare(b, a)
                if oa is Order.BEFORE:
                    assert ob is Order.AFTER
                if oa is Order.CONCURRENT:
                    assert ob is Order.CONCURRENT
        # transitivity of BEFORE
        for a in stamps:
            for b in stamps:
                for c in stamps:
                    if (compare(a, b) is Order.BEFORE
                            and compare(b, c) is Order.BEFORE):
                        assert compare(a, c) is Order.BEFORE

    @given(st.integers(1, 6), st.data())
    @settings(max_examples=50, deadline=None)
    def test_visibility_mask_matches_scalar(self, g, data):
        n = data.draw(st.integers(1, 10))
        creates, deletes, q = [], [], S(
            0, [data.draw(st.integers(0, 9)) for _ in range(g)], 0)
        for _ in range(n):
            creates.append(S(0, [data.draw(st.integers(0, 9))
                                 for _ in range(g)], 0))
            if data.draw(st.booleans()):
                deletes.append(S(0, [data.draw(st.integers(0, 9))
                                     for _ in range(g)], 0))
            else:
                deletes.append(None)
        mask = visibility_mask_np(pack_many(creates, g),
                                  pack_many(deletes, g), pack(q, g))
        for i in range(n):
            vis = compare(creates[i], q) is Order.BEFORE
            if deletes[i] is not None and compare(deletes[i], q) is Order.BEFORE:
                vis = False
            assert bool(mask[i]) == vis


def _rows_around(rng, g):
    """A query stamp (epoch 1) and stamps drawn around it: absent ones
    (``None``, packed as ``NO_STAMP``), its own vector from its own and
    from another gatekeeper, one component up or down, one up and
    another down (concurrent), a lower epoch with a larger vector, a
    higher epoch with a smaller one, and random draws; shuffled."""
    def mk(epoch, c, gk):
        c = tuple(int(x) for x in c)
        return Stamp(epoch, c, gk, c[gk])

    qc = rng.integers(1, 5, g)
    q = mk(1, qc, 0)
    stamps = [None] * 3 + [mk(1, qc, 0), mk(1, qc, g - 1)]
    for i in range(g):
        up, down = qc.copy(), qc.copy()
        up[i] += 1
        down[i] -= 1
        stamps += [mk(1, up, i), mk(1, down, i)]
        if g > 1:
            j = (i + 1) % g
            both = up.copy()
            both[j] -= 1
            stamps.append(mk(1, both, j))
    stamps += [mk(0, qc + 3, 0), mk(2, qc * 0, g - 1)]
    for _ in range(120):
        if rng.random() < 0.1:
            stamps.append(None)
        else:
            stamps.append(mk(int(rng.choice([0, 1, 1, 1, 2])),
                             rng.integers(0, 6, g), int(rng.integers(g))))
    return q, [stamps[i] for i in rng.permutation(len(stamps))]


FORMS = ["np_before", "jnp_before", "pairs_np", "pairs_jnp", "concurrent",
         "before_mask"]


class TestStampOrderCopies:
    """Every copy of ``row ≺ q`` over packed rows, and the packed
    concurrency test, against the kernel reference ``before_cm`` (on the
    transposed rows) and the scalar :func:`compare` on the stamps."""

    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("g", [1, 2, 4, 8])
    def test_form_agrees_with_reference_and_compare(self, g, form):
        rng = np.random.default_rng(100 * g + FORMS.index(form))
        cases = [_rows_around(rng, g) for _ in range(3)]
        for q, stamps in cases:
            rel = [None if s is None else compare(s, q) for s in stamps]
            assert None in rel
            assert {Order.BEFORE, Order.AFTER, Order.EQUAL} <= set(rel)
            assert {s.epoch for s in stamps if s} == {0, 1, 2}
            if g > 1:
                assert Order.CONCURRENT in rel
        packed = [(pack(q, g), pack_many(stamps, g)) for q, stamps in cases]
        # the scalar answers; packed rows carry no gatekeeper id, so an
        # equal vector is "possibly concurrent" there
        want_before = [np.array([s is not None
                                 and compare(s, q) is Order.BEFORE
                                 for s in stamps])
                       for q, stamps in cases]
        want_conc = [np.array([s is not None and compare(s, q) in
                               (Order.EQUAL, Order.CONCURRENT)
                               for s in stamps])
                     for q, stamps in cases]
        ref_before = [np.asarray(before_cm(jnp.asarray(rows.T),
                                           jnp.asarray(qr)))
                      for qr, rows in packed]
        for rb, wb in zip(ref_before, want_before):
            np.testing.assert_array_equal(rb, wb)

        if form in ("pairs_np", "pairs_jnp"):
            # one call over every case's rows, each row with its own query
            rows = np.concatenate([r for _, r in packed])
            qs = np.concatenate([np.tile(qr, (r.shape[0], 1))
                                 for qr, r in packed])
            xp = np if form == "pairs_np" else jnp
            got = np.asarray(writepath._before_pairs_xp(
                xp, xp.asarray(rows), xp.asarray(qs)))
            np.testing.assert_array_equal(got, np.concatenate(ref_before))
            np.testing.assert_array_equal(got, np.concatenate(want_before))
            return
        for (qr, rows), rb, wb, wc in zip(packed, ref_before, want_before,
                                          want_conc):
            if form == "concurrent":
                got = clock.concurrent_mask_np(rows, qr)
                np.testing.assert_array_equal(
                    np.asarray(clock.concurrent_mask(jnp.asarray(rows),
                                                     jnp.asarray(qr))), got)
                # same epoch, present, and neither row ≺ q nor q ≺ row
                q_before_row = np.asarray(jax.vmap(
                    before_cm, in_axes=(None, 0))(jnp.asarray(qr[:, None]),
                                                  jnp.asarray(rows)))[:, 0]
                same = (rows[:, 0] == qr[0]) & (rows[:, 0] != clock.NO_STAMP)
                np.testing.assert_array_equal(got,
                                              same & ~rb & ~q_before_row)
                np.testing.assert_array_equal(got, wc)
                continue
            if form == "np_before":
                got = clock._np_before(rows, qr)
            elif form == "jnp_before":
                got = np.asarray(clock._jnp_before(jnp.asarray(rows),
                                                   jnp.asarray(qr)))
            else:                       # the kernel body, interpreted
                got = np.asarray(mv_ops.before_mask(
                    jnp.asarray(rows), jnp.asarray(qr), interpret=True))
            np.testing.assert_array_equal(got, rb)
            np.testing.assert_array_equal(got, wb)


class TestOracle:
    def test_assert_and_query(self):
        o = TimelineOracle()
        a = o.create_event(S(0, [1, 0], 0))
        b = o.create_event(S(0, [0, 1], 1))
        assert o.query_order(a, b) is None
        o.assert_order(a, b)
        assert o.query_order(a, b) is Order.BEFORE
        assert o.query_order(b, a) is Order.AFTER

    def test_cycle_refused(self):
        o = TimelineOracle()
        a = o.create_event(S(0, [1, 0], 0))
        b = o.create_event(S(0, [0, 1], 1))
        o.assert_order(a, b)
        with pytest.raises(CycleError):
            o.assert_order(b, a)

    def test_transitive_through_explicit_edges(self):
        # paper §4.2: S0: T3 ≺ T5, S1: T4 ≺ T3  =>  T4 ≺ T5
        o = TimelineOracle()
        t3 = o.create_event(S(0, [3, 0, 0], 0))
        t4 = o.create_event(S(0, [0, 3, 0], 1))
        t5 = o.create_event(S(0, [0, 0, 3], 2))
        o.assert_order(t3, t5)
        o.assert_order(t4, t3)
        assert o.query_order(t4, t5) is Order.BEFORE

    def test_vclock_implied_transitivity(self):
        # paper §4.2: oracle orders <0,1> ≺ <1,0>; then <0,1> ≺ <2,0>
        # follows because <1,0> ≺ <2,0> by vector clocks.
        o = TimelineOracle()
        a = o.create_event(S(0, [0, 1], 1))
        b = o.create_event(S(0, [1, 0], 0))
        c = o.create_event(S(0, [2, 0], 0))
        o.assert_order(a, b)
        assert o.query_order(a, c) is Order.BEFORE

    def test_order_events_respects_kinds(self):
        # unordered (tx, prog) pair -> tx first (wall-clock rule §4.2)
        o = TimelineOracle()
        prog = S(0, [1, 0], 0)
        tx = S(0, [0, 1], 1)
        chain = o.order_events([prog, tx], [KIND_PROG, KIND_TX])
        assert chain == [tx.key(), prog.key()]

    def test_order_events_total_and_consistent(self):
        o = TimelineOracle()
        stamps = [S(0, [3, 4, 2], 1), S(0, [3, 1, 5], 2), S(0, [4, 4, 1], 0)]
        chain = o.order_events(stamps, [KIND_TX] * 3)
        assert len(chain) == 3
        # re-query: same total order, now committed
        for i in range(3):
            for j in range(i + 1, 3):
                assert o.query_order(chain[i], chain[j]) is Order.BEFORE

    def test_decisions_monotonic(self):
        o = TimelineOracle()
        a, b = S(0, [1, 0], 0), S(0, [0, 1], 1)
        first = o.order_events([a, b], [KIND_TX, KIND_TX])
        for _ in range(5):
            assert o.order_events([a, b], [KIND_TX, KIND_TX]) == first

    def test_gc_drops_old_events(self):
        o = TimelineOracle()
        a = o.create_event(S(0, [1, 1], 0))
        b = o.create_event(S(0, [9, 9], 0))
        horizon = S(0, [5, 5], 0)
        dropped = o.collect(horizon)
        assert dropped == 1
        assert a not in o.events and b in o.events

    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 5)),
                    min_size=2, max_size=7, unique=True))
    @settings(max_examples=60, deadline=None)
    def test_order_events_never_cycles(self, clocks):
        o = TimelineOracle()
        stamps = [S(0, list(c), i % 2) for i, c in enumerate(clocks)]
        chain = o.order_events(stamps, [KIND_TX] * len(stamps))
        # every adjacent pair committed; verify global consistency
        pos = {k: i for i, k in enumerate(chain)}
        for x in chain:
            for y in chain:
                if pos[x] < pos[y]:
                    assert o.query_order(y, x) is not Order.BEFORE
