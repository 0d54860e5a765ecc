"""Device-sharded columnar data plane: per-shard stamp blocks resident
per mesh device, visibility evaluated with ONE ``shard_map`` launch.

The host-global engines (``core.analytics.SnapshotEngine``,
``core.frontier.ShardPlan``) funnel every stamp comparison through
``clock._np_before`` on concatenated host arrays — one host's memory
bandwidth bounds snapshot assembly and plan builds.  This plane keeps a
*committed* copy of every partition's packed stamp tables on a fixed
mesh device and answers "row ≺ q" for ALL shards with a single sharded
kernel launch; only the boolean masks travel back to the host, and the
concurrent residue still takes the engines' existing single batched
oracle trip (refinement patches the host masks in place — broadcast,
not gathered).

Layout invariants (docs/ARCHITECTURE.md "Device-sharded columnar data
plane"):

* each shard owns one block ``(TABLES=4, B, C)`` — v_create, v_delete,
  e_create, e_delete stacked under ONE uniform capacity ``B`` (next
  pow2 of the largest table + slack) so the whole deployment is a
  dense ``(S_pad, 4, B, C)`` array and the launch needs no raggedness;
* devices own contiguous block ranges (device ``d`` holds blocks
  ``[d*spd, (d+1)*spd)``), matching ``NamedSharding(mesh, P("data"))``
  over axis 0;
* unused rows/blocks are ``NO_STAMP`` — ``_before`` maps them to False,
  so padding never flips a mask;
* maintenance follows the partitions' ``cursor()``/``CompactionEvent``
  contract: appends and in-place stamp patches become per-device row
  scatters (O(changed) per device, scatter index vectors padded to a
  pow2 bucket by repeating the last (idx, row) pair — duplicate
  scatters of an identical value are deterministic and bucketing bounds
  XLA specializations); a compaction remap re-uploads that shard's
  block (same O(live) cost class as the host engines' remap).

CPU vs TPU (``repro.kernels.backend``): the block kernel is
``clock._jnp_before`` (pure int32 jnp, bit-identical to ``_np_before``)
on the CPU and the compiled Pallas ``before`` kernel
(``kernels.mv_visibility``) on the TPU.  The host-global
path stays the equivalence oracle — see ``WeaverConfig.device_shard_columns``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..kernels import backend

TABLES = 4            # v_create, v_delete, e_create, e_delete
_MIN_BLOCK = 64


def visibility_launch(mesh, use_pallas: bool):
    """The jitted ``shard_map`` answering ``row ≺ q`` for (S, 4, B, C)
    stamp blocks sharded over ``mesh``'s ``"data"`` axis: the compiled
    Pallas ``before`` kernel when ``use_pallas``, else
    ``clock._jnp_before``.  Returns (S, 4, B) bool."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..core import clock
    from ..kernels.mv_visibility import ops

    def block_fn(blk, q):
        # blk (spd, 4, B, C) — this device's blocks; q (C,) replicated
        with jax.named_scope("weaver.mv_visibility"):
            rows = blk.reshape(-1, blk.shape[-1])
            if use_pallas:
                m = ops.before_mask(rows, q, interpret=False)
            else:
                m = clock._jnp_before(rows, q)
            return m.reshape(blk.shape[:-1])

    return jax.jit(jax.shard_map(block_fn, mesh=mesh,
                                 in_specs=(P("data"), P()),
                                 out_specs=P("data"), check_vma=False))


class DeviceColumnPlane:
    """Device-resident stamp blocks + one sharded visibility launch.

    One plane per deployment (``Weaver.device_plane``), on the
    ``WeaverConfig.n_chips`` devices of ``mesh``; both the
    snapshot engine and per-shard plan builds feed from it.  Blocks are
    keyed by column-table identity (``id(cols)`` with a strong ref), so
    dead shards, promoted backups and engine rebuilds never confuse
    block assignment.  ``sim`` (the deployment's simulator) lends its
    host profile to :meth:`sync` and :meth:`before_all`.
    """

    def __init__(self, n_gk: int, mesh, min_block: int = _MIN_BLOCK,
                 sim=None):
        self.sim = sim
        self.n_gk = n_gk
        self.c = n_gk + 1
        self.mesh = mesh
        self.n_dev = int(np.prod(list(mesh.shape.values())))
        self._devices = list(mesh.devices.flat)
        self.min_block = min_block
        self._idx: Dict[int, int] = {}     # id(cols) -> block index
        self._cols: List[object] = []      # block index -> cols (strong ref)
        self._consumed: List[Optional[List[int]]] = []  # per-block cursor
        self._cap = 0                      # B: rows per (table, block)
        self._spd = 0                      # blocks (shards) per device
        self._dev: List[object] = []       # per-device (spd, 4, B, C) arrays
        self._masks: Optional[np.ndarray] = None   # (S_pad, 4, B) bool
        self._masks_q: Optional[bytes] = None
        self._launch = None
        self.stats = {"rebuilds": 0, "row_updates": 0, "block_uploads": 0,
                      "launches": 0}

    # ---- residency maintenance -------------------------------------------

    def _host(self):
        return None if self.sim is None else self.sim.host

    def sync(self, shard_cols: Sequence) -> None:
        """Bring resident blocks up to date with each partition's change
        feed.  O(changed) per device for appends/patches; a compaction
        (or an unseen partition / capacity overflow) re-uploads or
        rebuilds."""
        h = self._host()
        if h is None:
            self._sync(shard_cols)
        else:
            h.call("plane_sync", self._sync, shard_cols)

    def _sync(self, shard_cols: Sequence) -> None:
        live = [c for c in shard_cols if c is not None]
        fresh = not self._dev
        for cols in live:
            bi = self._idx.get(id(cols))
            if bi is None or self._cols[bi] is not cols:
                fresh = True
                self._idx[id(cols)] = len(self._cols)
                self._cols.append(cols)
                self._consumed.append(None)
        if fresh or any(max(c.n_v, c.n_e) > self._cap for c in live):
            # size from the partitions alone: a floor at the old
            # capacity would double it on every rebuild
            self._rebuild(max(max(c.n_v, c.n_e) for c in self._cols))
            return
        for cols in live:
            self._sync_one(self._idx[id(cols)], cols)

    def _rebuild(self, need_rows: int) -> None:
        import jax

        s = len(self._cols)
        self._spd = max(1, -(-s // self.n_dev))
        self._cap = backend.pow2(max(self.min_block,
                                     need_rows + need_rows // 4))
        self._dev = []
        for d in range(self.n_dev):
            host = np.full((self._spd, TABLES, self._cap, self.c),
                           np.iinfo(np.int32).max, np.int32)
            for j in range(self._spd):
                bi = d * self._spd + j
                if bi < s:
                    self._fill_block(host[j], self._cols[bi])
            self._dev.append(jax.device_put(host, self._devices[d]))
        for bi, cols in enumerate(self._cols):
            self._consumed[bi] = cols.cursor()
        self._masks = None
        self._launch = None                 # shapes changed
        self.stats["rebuilds"] += 1

    @staticmethod
    def _fill_block(block: np.ndarray, cols) -> None:
        nv, ne = cols.n_v, cols.n_e
        if nv:
            block[0, :nv] = cols.v_create.view()
            block[1, :nv] = cols.v_delete.view()
        if ne:
            block[2, :ne] = cols.e_create.view()
            block[3, :ne] = cols.e_delete.view()

    def _sync_one(self, bi: int, cols) -> None:
        cur = self._consumed[bi]
        tgt = cols.cursor()
        if cur == tgt:
            return
        if cur is None or tgt[4] != cur[4]:
            # compaction remap (or never-synced block): the slot space
            # changed wholesale — re-upload this shard's live rows
            self._upload_block(bi, cols)
            self._consumed[bi] = tgt
            return
        nv0, ne0, lv0, le0, _ = cur
        ups: List[Tuple[int, np.ndarray, np.ndarray]] = []
        for t0, n0, n1, patch, p0, cview, dview in (
                (0, nv0, cols.n_v, cols.v_patch, lv0,
                 cols.v_create.view(), cols.v_delete.view()),
                (2, ne0, cols.n_e, cols.e_patch, le0,
                 cols.e_create.view(), cols.e_delete.view())):
            slots = {s for s in patch[p0:] if s < n0}
            if n1 > n0:
                slots.update(range(n0, n1))
            if not slots:
                continue
            idx = np.fromiter(sorted(slots), np.int64, len(slots))
            ups.append((t0, idx, np.ascontiguousarray(cview[idx])))
            ups.append((t0 + 1, idx, np.ascontiguousarray(dview[idx])))
        if ups:
            h = self._host()
            if h is None:
                self._scatter(bi, ups)
            else:
                h.call("plane_scatter", self._scatter, bi, ups)
        self._consumed[bi] = tgt
        self._masks = None

    def _scatter(self, bi: int,
                 ups: List[Tuple[int, np.ndarray, np.ndarray]]) -> None:
        import jax.numpy as jnp

        d, j = divmod(bi, self._spd)
        arr = self._dev[d]
        for t, idx, rows in ups:
            # pad to a pow2 bucket by repeating the last row (a duplicate
            # scatter of equal values is safe)
            idx_p = backend.pad_pow2(idx, idx[-1])
            rows_p = backend.pad_pow2(rows, rows[-1])
            arr = arr.at[j, t, jnp.asarray(idx_p), :].set(jnp.asarray(rows_p))
            self.stats["row_updates"] += idx.size
        self._dev[d] = arr

    def _upload_block(self, bi: int, cols) -> None:
        import jax.numpy as jnp

        d, j = divmod(bi, self._spd)
        host = np.full((TABLES, self._cap, self.c),
                       np.iinfo(np.int32).max, np.int32)
        self._fill_block(host, cols)
        self._dev[d] = self._dev[d].at[j].set(jnp.asarray(host))
        self._masks = None
        self.stats["block_uploads"] += 1

    # ---- the sharded launch ----------------------------------------------

    def _get_launch(self):
        if self._launch is None:
            self._launch = visibility_launch(self.mesh, backend.on_device())
        return self._launch

    def before_all(self, q: np.ndarray) -> None:
        """ONE sharded launch answering ``row ≺ q`` for every resident
        block; host-side masks cached until the next mutation or query
        change.  Call after :meth:`sync`."""
        q = np.asarray(q, np.int32)
        if self._masks is not None and self._masks_q == q.tobytes():
            return
        h = self._host()
        if h is None:
            self._launch_masks(q)
        else:
            h.call("plane_launch", self._launch_masks, q)

    def _launch_masks(self, q: np.ndarray) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = (self.n_dev * self._spd, TABLES, self._cap, self.c)
        sharding = NamedSharding(self.mesh, P("data"))
        garr = jax.make_array_from_single_device_arrays(
            shape, sharding, list(self._dev))
        out = self._get_launch()(garr, jnp.asarray(q))
        self._masks = np.asarray(out)
        self._masks_q = q.tobytes()
        self.stats["launches"] += 1

    def masks_for(self, cols) -> Tuple[np.ndarray, np.ndarray,
                                       np.ndarray, np.ndarray]:
        """(v_create≺q, v_delete≺q, e_create≺q, e_delete≺q) boolean masks
        for one partition, trimmed to its synced row counts."""
        bi = self._idx[id(cols)]
        cur = self._consumed[bi]
        m = self._masks[bi]
        nv, ne = cur[0], cur[1]
        return m[0, :nv], m[1, :nv], m[2, :ne], m[3, :ne]

    def has(self, cols) -> bool:
        bi = self._idx.get(id(cols))
        return (bi is not None and self._cols[bi] is cols
                and self._masks is not None)
