"""Per-architecture GNN smoke tests: a REDUCED config of each
architecture, one forward/train step on CPU, asserting output shapes + no
NaNs; and the neighbor sampler that feeds the minibatch path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.data import graphs as G
from repro.models import gnn
from repro.optim import AdamWConfig, adamw, make_train_step

RNG = np.random.default_rng(0)


def _check(x, shape=None):
    arr = np.asarray(x)
    if shape is not None:
        assert arr.shape == shape, (arr.shape, shape)
    assert np.all(np.isfinite(arr)), "NaN/Inf in output"


GNN_ARCHS = [a for a, s in ARCHS.items() if s.family == "gnn"]


def reduced_gnn(cfg: gnn.GNNConfig, d_feat=8, n_classes=3) -> gnn.GNNConfig:
    return dataclasses.replace(cfg, n_layers=2, d_hidden=16, d_feat=d_feat,
                               n_classes=n_classes)


@pytest.mark.parametrize("arch_id", GNN_ARCHS)
class TestGNNArchs:
    def test_forward_and_train_step(self, arch_id):
        spec = get_arch(arch_id)
        cfg = reduced_gnn(spec.config)
        n, e = 20, 60
        src, dst = G.random_graph(RNG, n, e)
        if cfg.kind == "dimenet":
            batch = {
                "species": jnp.asarray(RNG.integers(0, 8, n), jnp.int32),
                "pos": jnp.asarray(RNG.normal(size=(n, 3)), jnp.float32),
                "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
                "graph_ids": jnp.zeros((n,), jnp.int32), "n_graphs": 1,
                "labels": jnp.asarray([1.0], jnp.float32),
            }
            tin, tout = G.build_triplets(src, dst, max_per_edge=4)
            batch["trip_in"] = jnp.asarray(tin)
            batch["trip_out"] = jnp.asarray(tout)
        else:
            batch = {
                "x": jnp.asarray(RNG.normal(size=(n, cfg.d_feat)),
                                 jnp.float32),
                "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
                "graph_ids": jnp.zeros((n,), jnp.int32), "n_graphs": 1,
                "labels": jnp.asarray(RNG.integers(0, cfg.n_classes, n),
                                      jnp.int32),
            }
        params = gnn.init_params(jax.random.PRNGKey(0), cfg)
        out = gnn.forward(params, batch, cfg)
        _check(out)
        if cfg.task == "energy":
            assert out.shape == (1,)
        else:
            assert out.shape == (n, cfg.n_classes)
        step = make_train_step(lambda p, b: gnn.gnn_loss(p, b, cfg),
                               AdamWConfig(lr=1e-3, warmup_steps=1,
                                           total_steps=5))
        opt = adamw.init(params)
        p2, o2, metrics = step(params, opt, batch)
        _check(metrics["loss"])

    def test_molecule_batching(self, arch_id):
        spec = get_arch(arch_id)
        cfg = reduced_gnn(spec.config)
        mb = G.batch_molecules(RNG, n_graphs=4, n_nodes=6, n_edges=10,
                               d_feat=cfg.d_feat, with_pos=True)
        if cfg.kind == "dimenet":
            tin, tout = G.build_triplets(mb["edge_src"], mb["edge_dst"],
                                         max_per_edge=4)
            batch = dict(mb, trip_in=jnp.asarray(tin),
                         trip_out=jnp.asarray(tout),
                         species=jnp.asarray(mb["species"]),
                         pos=jnp.asarray(mb["pos"]))
            out = gnn.forward(gnn.init_params(jax.random.PRNGKey(0), cfg),
                              batch, cfg)
            assert out.shape == (4,)
        else:
            cfgg = dataclasses.replace(cfg, task="graph")
            out = gnn.forward(gnn.init_params(jax.random.PRNGKey(0), cfgg),
                              {**mb, "x": jnp.asarray(mb["x"]),
                               "edge_src": jnp.asarray(mb["edge_src"]),
                               "edge_dst": jnp.asarray(mb["edge_dst"]),
                               "graph_ids": jnp.asarray(mb["graph_ids"])},
                              cfgg)
            assert out.shape == (4, cfg.n_classes)
        _check(out)


class TestNeighborSampler:
    def test_neighbor_sampler(self):
        from repro.data.sampler import NeighborSampler
        src, dst = G.random_graph(RNG, 200, 2000)
        csr = G.build_csr(src, dst, 200)
        s = NeighborSampler(csr, fanouts=[5, 3], seed=0)
        max_n, max_e = NeighborSampler.max_sizes(8, [5, 3])
        sub = s.sample(np.arange(8), pad_to=(max_n, max_e))
        assert sub.node_ids.shape[0] == max_n
        assert sub.edge_src.shape[0] == max_e
        assert sub.n_real_nodes <= max_n
        # all real edges reference real nodes
        assert sub.edge_src[:sub.n_real_edges].max() < sub.n_real_nodes
