"""ShapeDtypeStruct stand-ins + step functions for every (arch x shape)
cell — nothing here allocates device memory; dims that jit's sharding
check requires to divide the mesh are padded exactly like the data
pipeline pads real batches.

``build(arch_id, shape_name, mesh, variant)`` returns a
:class:`Lowerable` with the function to jit, abstract args, input
shardings, and roofline metadata (MODEL_FLOPS per step).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.dist import sharding as sh
from repro.models import gnn, sasrec, transformer
from repro.optim import AdamWConfig, adamw, make_train_step


@dataclasses.dataclass
class Lowerable:
    fn: Callable
    args: tuple
    in_shardings: tuple
    meta: dict


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def _pad_to(x: int, m: int) -> int:
    return int(-(-x // m) * m)


def _mesh_total(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def _dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in sh.dp_axes(mesh)]))


def _safe(axes, dim, mesh) -> Optional[object]:
    """Return axes if dim divides the mesh extent of axes, else None."""
    if axes is None:
        return None
    tup = axes if isinstance(axes, tuple) else (axes,)
    ext = int(np.prod([mesh.shape[a] for a in tup]))
    return axes if dim % ext == 0 else None


OPT = AdamWConfig(lr=3e-4, warmup_steps=100, total_steps=10_000)


# ------------------------------------------------------------------ LM cells
def _lm_train(spec, dims, mesh, variant):
    cfg = spec.config
    if variant == "moe_a2a" and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, moe_impl="a2a"))
    if variant == "no_remat":
        cfg = dataclasses.replace(cfg, remat="none")
    batch, seq = dims["batch"], dims["seq"]
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda: adamw.init(params))
    bspec = {"tokens": _sds((batch, seq), jnp.int32),
             "labels": _sds((batch, seq), jnp.int32)}
    step = make_train_step(
        lambda p, b: transformer.lm_loss(p, b, cfg), OPT)

    rule = sh.lm_param_rule(mesh, fsdp=(variant != "no_fsdp"))
    pshard = sh.shardings_for_tree(params, rule, mesh)
    oshard = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        mu=sh.shardings_for_tree(params, rule, mesh),
        nu=sh.shardings_for_tree(params, rule, mesh))
    dp = sh.dp_axes(mesh)
    bshard = {"tokens": NamedSharding(mesh, P(_safe(dp, batch, mesh), None)),
              "labels": NamedSharding(mesh, P(_safe(dp, batch, mesh), None))}
    tokens = batch * seq
    meta = {
        "model_flops": 6.0 * cfg.n_active_params() * tokens,
        "model_flops_note": "6*N_active*D (train fwd+bwd)",
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
        "tokens_per_step": tokens,
    }
    return Lowerable(step, (params, opt, bspec), (pshard, oshard, bshard),
                     meta)


def _lm_prefill(spec, dims, mesh, variant):
    cfg = spec.config
    batch, seq = dims["batch"], dims["seq"]
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    fn = lambda p, t: transformer.prefill(p, t, cfg)
    rule = sh.lm_param_rule(mesh, fsdp=(variant != "no_fsdp"))
    pshard = sh.shardings_for_tree(params, rule, mesh)
    dp = sh.dp_axes(mesh)
    # SP: batch over DP, sequence over 'model'
    tspec = P(_safe(dp, batch, mesh), _safe("model", seq, mesh))
    tshard = NamedSharding(mesh, tspec)
    tokens = batch * seq
    meta = {
        "model_flops": 2.0 * cfg.n_active_params() * tokens,
        "model_flops_note": "2*N_active*D (prefill fwd)",
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
        "tokens_per_step": tokens,
    }
    return Lowerable(fn, (params, _sds((batch, seq), jnp.int32)),
                     (pshard, tshard), meta)


def _lm_decode(spec, dims, mesh, variant):
    cfg = spec.config
    if variant == "decode_splitk":
        cfg = dataclasses.replace(cfg, decode_attn="splitk")
    batch, seq = dims["batch"], dims["seq"]
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    window_bounded = variant == "window_cache"
    cache = jax.eval_shape(
        lambda: transformer.init_cache(cfg, batch, seq,
                                       window_bounded=window_bounded))
    fn = lambda p, c, t: transformer.decode_step(p, c, t, cfg)
    rule = sh.lm_param_rule(mesh, fsdp=(variant != "no_fsdp"))
    pshard = sh.shardings_for_tree(params, rule, mesh)
    dp = sh.dp_axes(mesh)
    bax = _safe(dp, batch, mesh)
    s_len = cache["k"].shape[2]
    sax = _safe("model", s_len, mesh)
    cshard = {"k": NamedSharding(mesh, P(None, bax, sax, None, None)),
              "v": NamedSharding(mesh, P(None, bax, sax, None, None)),
              "len": NamedSharding(mesh, P(bax))}
    tshard = NamedSharding(mesh, P(bax, None))
    meta = {
        "model_flops": 2.0 * cfg.n_active_params() * batch,
        "model_flops_note": "2*N_active per token (decode, B tokens)",
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
        "tokens_per_step": batch,
        "kv_bytes": float(np.prod(cache["k"].shape)) * 2 * 2,
    }
    return Lowerable(fn, (params, cache, _sds((batch, 1), jnp.int32)),
                     (pshard, cshard, tshard), meta)


# ----------------------------------------------------------------- GNN cells
def _gnn_batch_sds(cfg, dims, mesh, kind):
    total = _mesh_total(mesh)
    pad = lambda x: _pad_to(x, 512 if total <= 512 else total)
    if kind == "gnn_mol":
        n = pad(dims["batch"] * dims["n_nodes"])
        e = pad(dims["batch"] * dims["n_edges"])
        n_graphs = dims["batch"]
    else:
        n = pad(dims["n_nodes"])
        e = pad(dims["n_edges"])
        n_graphs = 1
    d_feat = dims["d_feat"]
    b = {
        "edge_src": _sds((e,), jnp.int32),
        "edge_dst": _sds((e,), jnp.int32),
        "graph_ids": _sds((n,), jnp.int32),
    }
    if cfg.kind == "dimenet":
        b["species"] = _sds((n,), jnp.int32)
        b["pos"] = _sds((n, 3), jnp.float32)
        t = pad(e * 8)                        # cutoff-capped triplet fan-in
        b["trip_in"] = _sds((t,), jnp.int32)
        b["trip_out"] = _sds((t,), jnp.int32)
        b["labels"] = _sds((n_graphs,), jnp.float32)
    else:
        b["x"] = _sds((n, d_feat), jnp.float32)
        if cfg.task == "graph":
            b["labels"] = _sds((n_graphs,), jnp.int32)
        else:
            b["labels"] = _sds((n,), jnp.int32)
            b["label_mask"] = _sds((n,), jnp.float32)
    return b, n, e, n_graphs


def _gnn_flops(cfg, n, e, t=0):
    """Analytic model flops for the GNN families (fwd+bwd = 3x fwd)."""
    h = cfg.d_hidden
    if cfg.kind == "gin":
        f = cfg.n_layers * (2 * n * h * h * 2 + 2 * e * h)
    elif cfg.kind == "pna":
        f = cfg.n_layers * (2 * e * (2 * h) * h + 4 * e * h
                            + 2 * n * (13 * h) * h)
    elif cfg.kind == "gat":
        f = cfg.n_layers * (2 * n * cfg.n_heads * h * h + 6 * e * h)
    else:  # dimenet
        f = cfg.n_layers * (2 * t * cfg.n_bilinear * h * h + 2 * e * h * h * 3)
    return 3.0 * f


def _gnn_train(spec, dims, mesh, variant, kind):
    cfg = spec.config
    cfg = dataclasses.replace(cfg, d_feat=dims["d_feat"],
                              n_classes=dims.get("n_classes",
                                                 cfg.n_classes),
                              constrain_acts={"gnn_constrained": "all",
                                              "gnn_nodes": "nodes"}.get(
                                                  variant, ""))
    if kind == "gnn_mol" and cfg.kind != "dimenet":
        cfg = dataclasses.replace(cfg, task="graph")
    bsds, n, e, n_graphs = _gnn_batch_sds(cfg, dims, mesh, kind)
    params = jax.eval_shape(
        lambda: gnn.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda: adamw.init(params))

    def loss_fn(p, b):
        return gnn.gnn_loss(p, {**b, "n_graphs": n_graphs}, cfg)

    step = make_train_step(loss_fn, OPT)
    rule = sh.gnn_param_rule(mesh)
    pshard = sh.shardings_for_tree(params, rule, mesh)
    oshard = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        mu=sh.shardings_for_tree(params, rule, mesh),
        nu=sh.shardings_for_tree(params, rule, mesh))
    axes = tuple(mesh.axis_names)
    bspec = sh.gnn_batch_spec(mesh, full_graph=True)
    bshard = {}
    for k, v in bsds.items():
        spec_k = bspec.get(k, P(axes))
        if v.shape[0] == n_graphs and n_graphs % _mesh_total(mesh) != 0:
            spec_k = P(*([None] * len(v.shape)))      # tiny: replicate
        bshard[k] = NamedSharding(mesh, spec_k)
    t = bsds.get("trip_in")
    meta = {
        "model_flops": _gnn_flops(cfg, n, e,
                                  t.shape[0] if t is not None else 0),
        "model_flops_note": "analytic per-family (fwd+bwd=3x fwd)",
        "n_nodes": n, "n_edges": e,
    }
    return Lowerable(step, (params, opt, bsds), (pshard, oshard, bshard),
                     meta)


# -------------------------------------------------------------- recsys cells
def _rec_train(spec, dims, mesh, variant):
    cfg = spec.config
    batch, seq = dims["batch"], cfg.seq_len
    params = jax.eval_shape(
        lambda: sasrec.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda: adamw.init(params))
    bsds = {k: _sds((batch, seq), jnp.int32) for k in ("hist", "pos", "neg")}
    step = make_train_step(lambda p, b: sasrec.bce_loss(p, b, cfg), OPT)
    rule = sh.recsys_param_rule(mesh)
    pshard = sh.shardings_for_tree(params, rule, mesh)
    oshard = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        mu=sh.shardings_for_tree(params, rule, mesh),
        nu=sh.shardings_for_tree(params, rule, mesh))
    dp = sh.dp_axes(mesh)
    bshard = {k: NamedSharding(mesh, P(_safe(dp, batch, mesh), None))
              for k in bsds}
    d = cfg.d_embed
    attn_f = cfg.n_blocks * (4 * batch * seq * d * d + 2 * batch * seq * seq * d)
    emb_f = 2 * batch * seq * d * 3
    meta = {"model_flops": 3.0 * (attn_f + emb_f),
            "model_flops_note": "analytic fwd+bwd",
            "tokens_per_step": batch * seq}
    return Lowerable(step, (params, opt, bsds), (pshard, oshard, bshard),
                     meta)


def _rec_serve(spec, dims, mesh, variant):
    cfg = spec.config
    batch, seq = dims["batch"], cfg.seq_len
    params = jax.eval_shape(
        lambda: sasrec.init_params(jax.random.PRNGKey(0), cfg))

    from repro import dist as _dist
    from jax.sharding import PartitionSpec as _P
    dp = sh.dp_axes(mesh)
    vshard = mesh.shape["model"]

    def fn(p, hist):
        scores = sasrec.score_catalog(p, hist, cfg)
        # (B, V): batch over DP, catalog over 'model'
        scores = _dist.constrain(
            scores, lambda m: _P(sh.dp_axes(m), "model"))

        # Distributed top-k.  XLA's sort partitioning REPLICATES the
        # operand (976 GiB/device for the bulk cell, measured), so stage 1
        # runs as an explicit shard_map: local top-100 per catalog shard
        # with globally-offset indices, then a cheap merge top-100 over
        # the (B, shards*100) gathered candidates.
        def local_topk(sc):                      # (B/dp, V/vshard)
            v_loc, i_loc = jax.lax.top_k(sc, 100)
            off = jax.lax.axis_index("model") * sc.shape[-1]
            return v_loc, (i_loc + off).astype(jnp.int32)

        v_loc, i_loc = jax.shard_map(
            local_topk, mesh=mesh,
            in_specs=_P(dp, "model"),
            out_specs=(_P(dp, "model"), _P(dp, "model")))(scores)
        # (B, vshard*100) candidates, batch-sharded; merge is tiny
        v_top, pos = jax.lax.top_k(v_loc, 100)
        idx = jnp.take_along_axis(i_loc, pos, axis=1)
        return v_top, idx

    rule = sh.recsys_param_rule(mesh)
    pshard = sh.shardings_for_tree(params, rule, mesh)
    dp = sh.dp_axes(mesh)
    hshard = NamedSharding(mesh, P(_safe(dp, batch, mesh), None))
    rows = sasrec.table_rows(cfg)
    d = cfg.d_embed
    f = (cfg.n_blocks * (4 * batch * seq * d * d + 2 * batch * seq * seq * d)
         + 2 * batch * rows * d)
    meta = {"model_flops": float(f),
            "model_flops_note": "encode + full-catalog dot",
            "catalog_rows": rows}
    return Lowerable(fn, (params, _sds((batch, seq), jnp.int32)),
                     (pshard, hshard), meta)


def _rec_retrieval(spec, dims, mesh, variant):
    cfg = spec.config
    batch, seq = dims["batch"], cfg.seq_len
    n_cand = dims["n_candidates"]
    params = jax.eval_shape(
        lambda: sasrec.init_params(jax.random.PRNGKey(0), cfg))
    fn = lambda p, h, c: sasrec.score_candidates(p, h, c, cfg)
    rule = sh.recsys_param_rule(mesh)
    pshard = sh.shardings_for_tree(params, rule, mesh)
    hshard = NamedSharding(mesh, P(None, None))
    cshard = NamedSharding(mesh, P(None, _safe("model", n_cand, mesh)))
    d = cfg.d_embed
    f = cfg.n_blocks * (4 * batch * seq * d * d
                        + 2 * batch * seq * seq * d) + 2 * batch * n_cand * d
    meta = {"model_flops": float(f),
            "model_flops_note": "encode + 1M-candidate batched dot"}
    return Lowerable(fn, (params, _sds((batch, seq), jnp.int32),
                          _sds((batch, n_cand), jnp.int32)),
                     (pshard, hshard, cshard), meta)


# ------------------------------------------------------------------ dispatch
def build(arch_id: str, shape_name: str, mesh: Mesh,
          variant: str = "baseline") -> Lowerable:
    spec = get_arch(arch_id)
    shape = spec.shapes[shape_name]
    if shape.skip:
        raise ValueError(f"cell {arch_id}/{shape_name} skipped: {shape.skip}")
    kind = shape.kind
    if kind == "lm_train":
        return _lm_train(spec, shape.dims, mesh, variant)
    if kind == "lm_prefill":
        return _lm_prefill(spec, shape.dims, mesh, variant)
    if kind == "lm_decode":
        return _lm_decode(spec, shape.dims, mesh, variant)
    if kind in ("gnn_full", "gnn_mini", "gnn_mol"):
        return _gnn_train(spec, shape.dims, mesh, variant, kind)
    if kind == "rec_train":
        return _rec_train(spec, shape.dims, mesh, variant)
    if kind == "rec_serve":
        return _rec_serve(spec, shape.dims, mesh, variant)
    if kind == "rec_retrieval":
        return _rec_retrieval(spec, shape.dims, mesh, variant)
    raise ValueError(f"unknown shape kind {kind}")
