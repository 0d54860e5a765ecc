"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp ref.py oracles, plus hypothesis property tests."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import clock
from repro.core.clock import Stamp


# ------------------------------------------------------------ mv_visibility
class TestMVVisibility:
    def _rand_rows(self, rng, n, g, frac_nostamp=0.3):
        rows = rng.integers(0, 6, size=(n, g + 1)).astype(np.int32)
        rows[:, 0] = rng.integers(0, 2, size=n)        # epochs
        no = rng.random(n) < frac_nostamp
        rows[no] = clock.NO_STAMP
        return rows

    @pytest.mark.parametrize("n,g", [(7, 1), (64, 2), (300, 3), (1024, 4),
                                     (2500, 8)])
    def test_matches_ref_and_core(self, n, g):
        from repro.kernels.mv_visibility import ops
        rng = np.random.default_rng(n * 31 + g)
        creates = rng.integers(0, 6, size=(n, g + 1)).astype(np.int32)
        creates[:, 0] = 0
        deletes = self._rand_rows(rng, n, g, frac_nostamp=0.5)
        q = np.asarray([0] + list(rng.integers(0, 6, g)), np.int32)
        got = np.asarray(ops.visibility_mask(creates, deletes, q))
        ref = np.asarray(ops.visibility_mask(creates, deletes, q,
                                             use_ref=True))
        core = clock.visibility_mask_np(creates, deletes, q)
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(got, core)

    @given(st.integers(1, 5), st.integers(1, 200), st.integers(0, 10**6))
    @settings(max_examples=30, deadline=None)
    def test_property_matches_core(self, g, n, seed):
        from repro.kernels.mv_visibility import ops
        rng = np.random.default_rng(seed)
        creates = rng.integers(0, 4, size=(n, g + 1)).astype(np.int32)
        creates[:, 0] = rng.integers(0, 2, size=n)
        deletes = self._rand_rows(rng, n, g)
        q = rng.integers(0, 4, size=g + 1).astype(np.int32)
        got = np.asarray(ops.visibility_mask(creates, deletes, q,
                                             block_n=128))
        core = clock.visibility_mask_np(creates, deletes, q)
        np.testing.assert_array_equal(got, core)


# -------------------------------------------------------------- segment_mp
class TestSegmentMP:
    @pytest.mark.parametrize("n,e,d,f,dtype", [
        (64, 256, 16, 32, jnp.float32),
        (128, 1000, 64, 64, jnp.float32),
        (300, 2000, 32, 8, jnp.float32),
        (128, 512, 128, 128, jnp.bfloat16),
        (17, 3, 8, 16, jnp.float32),          # tiny/ragged
    ])
    def test_matches_ref(self, n, e, d, f, dtype):
        from repro.kernels.segment_mp import ops
        from repro.kernels.segment_mp.ref import segment_matmul_reduce_ref
        rng = np.random.default_rng(e + d)
        x = jnp.asarray(rng.normal(size=(n, d)), dtype)
        w = jnp.asarray(rng.normal(size=(d, f)), dtype)
        src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
        dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
        got = ops.segment_matmul_reduce(x, w, src, dst, n,
                                        block_n=32, block_e=64)
        # bf16: the kernel accumulates fp32 across tiles, so compare to
        # the fp32 ground truth with bf16-eps-scaled tolerance
        ref = segment_matmul_reduce_ref(
            x.astype(jnp.float32), w.astype(jnp.float32), src, dst, n)
        if dtype == jnp.bfloat16:
            tol = dict(rtol=3e-2, atol=3e-1)
        else:
            tol = dict(rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **tol)

    def test_skewed_degrees(self):
        """Power-law dst distribution (one hub node)."""
        from repro.kernels.segment_mp import ops
        from repro.kernels.segment_mp.ref import segment_matmul_reduce_ref
        rng = np.random.default_rng(0)
        n, e, d, f = 100, 3000, 16, 16
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(d, f)), jnp.float32)
        src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
        dst = np.where(rng.random(e) < 0.5, 7,
                       rng.integers(0, n, e)).astype(np.int32)
        got = ops.segment_matmul_reduce(x, w, src, jnp.asarray(dst), n,
                                        block_n=32, block_e=128)
        ref = segment_matmul_reduce_ref(x, w, src, jnp.asarray(dst), n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_mp_seam_switches(self):
        """repro.models.mp routes through the kernel when enabled."""
        from repro.models import mp
        rng = np.random.default_rng(1)
        n, e, d, f = 40, 200, 8, 8
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(d, f)), jnp.float32)
        src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
        dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
        base = mp.propagate_matmul(x, w, src, dst, n)
        mp.set_use_pallas(True)
        try:
            fused = mp.propagate_matmul(x, w, src, dst, n)
        finally:
            mp.set_use_pallas(False)
        np.testing.assert_allclose(np.asarray(base), np.asarray(fused),
                                   rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- segment_sum
def _sorted_segments(rng, n, e, hub=None, hub_share=0.0, lo=0, hi=None):
    """dst-sorted ``(vals, dst)``: dst uniform in ``[lo, hi)``, a share of
    the edges on one ``hub``; vals positive float32."""
    dst = rng.integers(lo, n if hi is None else hi, e)
    if hub is not None:
        dst[rng.random(e) < hub_share] = hub
    dst = np.sort(dst).astype(np.int32)
    return rng.random(e).astype(np.float32) + 0.01, dst


def _segment_sum_f64(vals, dst, n):
    out = np.zeros(n)
    np.add.at(out, dst, vals.astype(np.float64))
    return out


def _tiled_segment_sum(vals, dst, n, bn, be):
    from repro.kernels.segment_sum import ops
    from repro.kernels.segment_sum.kernel import tiled_segment_sum
    lay = ops.tile_layout(dst, n, bn, be)
    return np.asarray(tiled_segment_sum(
        jnp.asarray(ops.pad_edges(vals, lay, 0.0)),
        jnp.asarray(ops.pad_edges(dst, lay, -1)),
        jnp.asarray(lay.tile_block), jnp.asarray(lay.tile_chunk),
        jnp.asarray(lay.n_used), n, block_n=bn, block_e=be))


class TestSegmentSum:
    @pytest.mark.parametrize("case,n,e,bn,be", [
        ("hub", 300, 3000, 128, 256),          # a hub over many tiles
        ("gaps", 700, 900, 128, 256),          # empty blocks and vertices
        ("ragged", 1000, 2500, 128, 512),      # E % BE, N % BN both != 0
        ("no_edges", 200, 0, 128, 256),
        ("wide", 2048, 6000, 256, 1024),
    ])
    def test_matches_f64(self, case, n, e, bn, be):
        rng = np.random.default_rng(e + n)
        if case == "hub":
            vals, dst = _sorted_segments(rng, n, e, hub=131, hub_share=0.6)
        elif case == "gaps":
            # edges only into [130, 200) and [500, 510): blocks 2, 4, 5
            # and most vertices receive nothing
            v1, d1 = _sorted_segments(rng, n, e - 100, lo=130, hi=200)
            v2, d2 = _sorted_segments(rng, n, 100, lo=500, hi=510)
            vals, dst = np.r_[v1, v2], np.r_[d1, d2]
        else:
            vals, dst = _sorted_segments(rng, n, e)
        got = _tiled_segment_sum(vals, dst, n, bn, be)
        want = _segment_sum_f64(vals, dst, n)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert np.all(got[want == 0] == 0)

    @pytest.mark.parametrize("n,e,bn,be,hub", [
        (300, 3000, 128, 256, 131), (1000, 2500, 128, 512, None),
        (200, 0, 128, 256, None), (5000, 700, 128, 128, 4999)])
    def test_layout_counts_every_edge_once(self, n, e, bn, be, hub):
        """Each edge lies in the chunk of exactly one used tile of its own
        block, within ``T_max`` tiles, and every block has a tile."""
        from repro.kernels.segment_sum import ops
        rng = np.random.default_rng(n + e)
        _, dst = _sorted_segments(rng, n, e, hub=hub, hub_share=0.5)
        lay = ops.tile_layout(dst, n, bn, be)
        n_blocks = -(-n // bn)
        assert lay.tile_block.size == -(-e // be) + n_blocks
        used = int(lay.n_used[0])
        tb, tc = lay.tile_block[:used], lay.tile_chunk[:used]
        assert np.all(np.diff(tb) >= 0)
        assert set(tb.tolist()) == set(range(n_blocks))
        assert not np.any((np.diff(tb) == 0) & (np.diff(tc) <= 0))
        assert np.all((tc >= 0) & (tc * be < lay.e_pad))
        hits = np.zeros(e, np.int64)
        for b, c in zip(tb.tolist(), tc.tolist()):
            pos = np.arange(c * be, min((c + 1) * be, e))
            hits[pos[dst[pos] // bn == b]] += 1
        np.testing.assert_array_equal(hits, 1)
        assert lay.masked_slots == used * be - e

    @pytest.mark.parametrize("n,e,be", [(3000, 2048, 1024), (1, 256, 128),
                                        (700, 512, 256),
                                        (1 << 17, 1024, 1024)])
    def test_table_gather_is_bit_equal(self, n, e, be):
        """The MXU gather returns ``table[idx]`` bit for bit, for values
        over many binades, zero, the largest float and a table whose size
        is not a multiple of 128."""
        from repro.kernels.segment_sum.kernel import table_gather
        rng = np.random.default_rng(n + e)
        table = ((1 + rng.random(n)) * 2.0 ** rng.integers(-103, 127, n)
                 ).astype(np.float32)
        table[0] = 0.0
        table[-1] = np.finfo(np.float32).max
        idx = rng.integers(0, n, e).astype(np.int32)
        idx[:2] = [0, n - 1]
        got = np.asarray(table_gather(jnp.asarray(table), jnp.asarray(idx),
                                      block_e=be))
        np.testing.assert_array_equal(got.view(np.int32),
                                      table[idx].view(np.int32))

    def test_split_bf16_parts_sum_exactly(self):
        from repro.kernels.segment_sum.kernel import split_bf16
        rng = np.random.default_rng(3)
        # down to 2**-103, below which the last part may be subnormal
        x = np.r_[(1 + rng.random(4096)) * 2.0 ** rng.integers(-103, 127,
                                                               4096),
                  2.0 ** -103,
                  -rng.random(64), 0.0, 1.0 - 2.0 ** -24].astype(np.float32)
        p = np.asarray(split_bf16(jnp.asarray(x))).astype(np.float32)
        np.testing.assert_array_equal(((p[2] + p[1]) + p[0]).view(np.int32),
                                      x.view(np.int32))

    @pytest.mark.parametrize("chips", [2, 4])
    @pytest.mark.parametrize("n,e,bn,be,hub,hi", [
        (3000, 20000, 256, 1024, 1500, None),  # a hub, ragged N and E
        (1900, 9000, 256, 1024, None, 1000),   # upper ranges get nothing
        (5000, 700, 128, 128, 4999, None),     # more blocks than chunks
        (600, 0, 128, 256, None, None)])
    def test_split_covers_every_edge_and_vertex_once(self, chips, n, e, bn,
                                                     be, hub, hi):
        """The chips' ranges cover every vertex once, their slices every
        CSC edge once (each edge lies in exactly one used tile of its own
        block, on the chip that owns its dst), each slice is the
        whole-graph chunks at their places, and the slots leave the
        layout's room."""
        from repro.kernels.segment_sum import ops
        rng = np.random.default_rng(n + e + chips)
        _, dst = _sorted_segments(rng, n, e, hub=hub, hub_share=0.3, hi=hi)
        src = rng.integers(0, n, e).astype(np.int32)
        sp = ops.split_layout(src, dst, n, chips, bn, be)
        nl, slots = sp.n_local, sp.src.size // chips
        # the fewest whole blocks per chip that hold every vertex
        assert nl % bn == 0 and chips * nl >= n > chips * (nl - bn)
        owner = np.repeat(np.arange(chips), nl)[:n]
        np.testing.assert_array_equal(np.bincount(owner, minlength=chips)
                                      .sum(), n)
        np.testing.assert_array_equal(
            sp.edges, np.bincount(owner[dst], minlength=chips))
        t = sp.tile_block.size // chips
        hits = np.zeros(e, np.int64)
        for d in range(chips):
            ld = sp.dst[d * slots:(d + 1) * slots]
            real = ld != ops.PAD_DST
            k = int(real.sum())
            assert np.all(real[:k]) and np.all(np.diff(ld[:k]) >= 0)
            # the slice starts on the whole-graph chunk of its range's
            # first edge
            a = int(np.searchsorted(dst, d * nl)) // be * be
            np.testing.assert_array_equal(ld[:k] + d * nl, dst[a:a + k])
            np.testing.assert_array_equal(sp.src[d * slots:d * slots + k],
                                          src[a:a + k])
            used = int(sp.n_used[d])
            tb = sp.tile_block[d * t:d * t + used]
            tc = sp.tile_chunk[d * t:d * t + used]
            assert set(tb.tolist()) == set(range(nl // bn))
            for b, c in zip(tb.tolist(), tc.tolist()):
                pos = np.arange(c * be, min((c + 1) * be, k))
                mine = pos[(ld[pos] >= 0) & (ld[pos] // bn == b)]
                hits[a + mine] += 1
        np.testing.assert_array_equal(hits, 1)
        mean = -(-(-(-e // be)) // chips)
        assert slots // be >= mean + max(1, -(-mean // 4))

    def test_layout_refuses_unsorted_dst(self):
        from repro.kernels.segment_sum import ops
        with pytest.raises(ValueError, match="non-decreasing"):
            ops.tile_layout(np.array([3, 1, 2], np.int32), 4)

