"""Pallas TPU kernels for the database's compute hot-spots.

Each kernel package has: kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper, layout prep), ref.py (pure-jnp oracle).
All are validated in interpret=True mode against ref.py across
shape/dtype sweeps (tests/test_kernels.py); segment_sum, which replaces
XLA operations, against float64 numpy and XLA's own gather instead.

  mv_visibility   — batched refinable-timestamp snapshot masks (the
                    paper's multi-version read path, DESIGN.md §3)
  segment_mp      — fused gather->matmul->segment-reduce message passing
                    (SpMM regime: GIN/PNA/GAT aggregation, node programs)
  segment_sum     — PageRank's edge pass on the TPU: ``table_gather``
                    (exact MXU gather from a VMEM-resident table) and
                    ``tiled_segment_sum`` (float32 dst-tiled segment sum
                    over CSC edges, replacing the sorted scatter-add)
"""
