"""Explicit shard_map collectives (MoE all-to-all, hierarchical grad
sync, cross-pod allreduce).

Overflow semantics of the MoE all-to-all (parity with the jit-level
scatter path)
-------------------------------------------------------------------
The jit-level scatter path (``models/moe.moe_block``) drops (token,
slot) pairs per **global expert** once the expert's capacity ``C`` is
full, in global flattened ``(T, k)`` order.  The all-to-all dispatch
only sees device-local tokens, so a naive local capacity check drops a
*different* set of pairs under overflow.  ``moe_alltoall_block`` now
reproduces the scatter semantics exactly (``overflow="global"``, the
default): each device computes its tokens' **global** position inside
their expert with one extra all-gather of the per-expert local counts
(an ``(n_devices, E)`` int32 exchange — negligible next to the
activation all-to-alls) and applies the same ``pos < capacity`` cut.
The wire buffer ``c_dev`` is then clamped to the static bound
``min(t_loc*k, e_loc*capacity)`` so no kept pair can secondarily
overflow a (source device, destination shard) slab.  The legacy
per-(source device, destination shard) drop rule survives behind
``overflow="local"`` for callers that prefer a smaller wire buffer over
drop parity; any other value, or ``overflow="global"`` without a
``capacity``, is an explicit config error raised at trace time.

Two-phase exact sizing
----------------------
The static bound is safe but often far larger than what any (source
device, destination shard) slab actually carries — the all-to-all then
ships mostly zeros.  :func:`moe_alltoall_exact_c_dev` is a cheap
phase-1 counting pass over the *logits only* (same routing math, same
keep mask — factored into ``_route_keep`` so the two cannot drift)
that returns the exact max kept-pairs-per-slab, rounded up to a
multiple of 8 for lane alignment.  It must run OUTSIDE jit — the count
becomes a static wire-buffer shape — and raises on tracer input.
Passing its result with ``exact_c_dev=True`` skips the static clamp;
outputs are elementwise identical for ANY ``c_dev`` >= the true max.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _route_keep(logits_l, k, e, e_loc, n_model, tok_axes, mesh,
                n_tok_dev, capacity, overflow):
    """Per-device routing shared by the dispatch body and the phase-1
    sizing pass: normalized router weights, flat expert ids, destination
    shards, per-expert positions, and the ``overflow="global"`` keep
    mask (all-ones for ``"local"`` — its cut is per-destination and
    applied by the caller)."""
    probs = jax.nn.softmax(logits_l.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, k)              # (t_loc, k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    flat_e = idx.reshape(-1)                            # (N = t_loc*k,)
    n = flat_e.shape[0]
    dest = flat_e // e_loc
    # local position of each pair inside its expert (stable sort:
    # ties keep local flattened (token, slot) order)
    order_e = jnp.argsort(flat_e)
    e_sorted = flat_e[order_e]
    starts_e = jnp.searchsorted(
        e_sorted, jnp.arange(e, dtype=e_sorted.dtype))
    pos_e_sorted = jnp.arange(n, dtype=jnp.int32) - starts_e[e_sorted]
    pos_e = jnp.zeros((n,), jnp.int32).at[order_e].set(pos_e_sorted)

    if overflow == "global":
        # exclusive prefix of per-expert counts over all devices in
        # global token order: device rank = row-major index over the
        # token sharding axes, matching the (dp..., model) layout of
        # the global token array
        counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
        all_counts = jax.lax.all_gather(counts, tok_axes)
        all_counts = all_counts.reshape(n_tok_dev, e)
        my = jnp.int32(0)
        for a in tok_axes:
            my = my * int(mesh.shape[a]) + jax.lax.axis_index(a)
        mask = (jnp.arange(n_tok_dev, dtype=jnp.int32)
                < my)[:, None].astype(jnp.int32)
        prefix = jnp.sum(all_counts * mask, axis=0)     # (e,)
        keep = prefix[flat_e] + pos_e < capacity        # == scatter path
    else:
        keep = jnp.ones((n,), bool)                     # cut per-dest later
    return weights, flat_e, dest, pos_e, keep


def moe_alltoall_block(xf, logits, w_gate, w_up, w_down, mesh, top_k,
                      c_dev, local_capacity_factor=2.0, capacity=None,
                      overflow="global", exact_c_dev=False):
    """Expert-parallel MoE dispatch via explicit all-to-all.

    Tokens are sharded over (dp axes, 'model'); the expert axis of the
    weight tensors is sharded over 'model' (replicated across dp).  Each
    device routes its local (token, slot) pairs to the model shard that
    owns the chosen expert with a sort-based dispatch (same MegaBlocks
    trick as the jit-level scatter path), exchanges (n_model, c_dev, d)
    buffers with ONE all_to_all each way, and combines locally with its
    own router weights — so only token activations cross the wire.

    Capacity semantics (see module docstring): ``overflow="global"``
    (default) drops per global expert at ``capacity`` exactly like the
    scatter path — elementwise-equal outputs in and out of the overflow
    regime; ``overflow="local"`` keeps the legacy per-(source device,
    destination shard) drop at ``max(c_dev, ceil(t_loc*k*
    local_capacity_factor/n_model))``, which agrees with the scatter
    path only when capacity is ample.

    ``exact_c_dev=True`` trusts the caller's ``c_dev`` instead of
    clamping it up to the static bound — pass the result of the
    phase-1 :func:`moe_alltoall_exact_c_dev` counting pass, which
    guarantees every kept pair fits its slab.
    """
    import math

    from jax.sharding import PartitionSpec as P

    from .sharding import dp_axes

    n_model = int(mesh.shape["model"])
    e = w_gate.shape[0]
    assert e % n_model == 0, (e, n_model)
    e_loc = e // n_model
    dp_names = dp_axes(mesh)
    tok_axes = tuple(dp_names) + ("model",)
    n_dp = int(math.prod(int(mesh.shape[a]) for a in dp_names)) \
        if dp_names else 1
    t_loc = int(xf.shape[0]) // (n_dp * n_model)
    if overflow == "global":
        if capacity is None:
            raise ValueError(
                "moe_alltoall_block(overflow='global') needs the global "
                "per-expert `capacity` used by the scatter path; pass it, "
                "or opt into the divergent overflow='local' semantics")
        if exact_c_dev:
            # phase-1 counted the true max kept per slab; a larger
            # buffer only ships more zeros, never changes the output
            c_dev = min(int(c_dev),
                        min(t_loc * int(top_k), e_loc * int(capacity)))
        else:
            # every kept pair must fit its (source device, dest shard)
            # slab: a device keeps at most min(its local pairs,
            # e_loc*capacity) pairs for one destination shard — a STATIC
            # bound, so parity needs no runtime assertion
            c_dev = max(int(c_dev),
                        min(t_loc * int(top_k), e_loc * int(capacity)))
    elif overflow == "local":
        c_dev = max(int(c_dev),
                    math.ceil(t_loc * int(top_k)
                              * float(local_capacity_factor) / n_model))
    else:
        raise ValueError(f"unknown overflow mode {overflow!r} "
                         "(expected 'global' or 'local')")
    n_tok_dev = n_dp * n_model

    def body(xf_l, logits_l, wg, wu, wd):
        t_loc, d = xf_l.shape
        k = top_k
        weights, flat_e, dest, pos_e, keep = _route_keep(
            logits_l, k, e, e_loc, n_model, tok_axes, mesh, n_tok_dev,
            capacity, overflow)
        n = flat_e.shape[0]

        # position among the KEPT pairs of each destination shard
        d2 = jnp.where(keep, dest, n_model)               # dropped -> tail
        order_d = jnp.argsort(d2)
        d2s = d2[order_d]
        starts_d = jnp.searchsorted(
            d2s, jnp.arange(n_model, dtype=d2s.dtype))
        pos_d_sorted = (jnp.arange(n, dtype=jnp.int32)
                        - starts_d[jnp.minimum(d2s, n_model - 1)])
        pos = jnp.zeros((n,), jnp.int32).at[order_d].set(pos_d_sorted)
        if overflow == "local":
            keep = pos < c_dev
        pos_c = jnp.where(keep, pos, c_dev)             # overflow slot
        token_of = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)

        send_x = jnp.zeros((n_model, c_dev + 1, d), xf_l.dtype)
        send_x = send_x.at[dest, pos_c].set(
            xf_l[token_of] * keep[:, None].astype(xf_l.dtype))
        send_e = jnp.zeros((n_model, c_dev + 1), jnp.int32)
        send_e = send_e.at[dest, pos_c].set(flat_e % e_loc)

        recv_x = jax.lax.all_to_all(send_x[:, :c_dev], "model", 0, 0)
        recv_e = jax.lax.all_to_all(send_e[:, :c_dev], "model", 0, 0)

        rx = recv_x.reshape(n_model * c_dev, d)
        re = recv_e.reshape(n_model * c_dev)
        g = jnp.einsum("nd,ndf->nf", rx, wg[re])
        u = jnp.einsum("nd,ndf->nf", rx, wu[re])
        out = jnp.einsum("nf,nfd->nd", jax.nn.silu(g) * u, wd[re])

        back = jax.lax.all_to_all(
            out.reshape(n_model, c_dev, d), "model", 0, 0)
        back_flat = jnp.concatenate(
            [back.reshape(n_model * c_dev, d),
             jnp.zeros((1, d), back.dtype)], axis=0)
        slot = jnp.where(keep, dest * c_dev + pos_c, n_model * c_dev)
        per_slot = back_flat[slot]                      # (N, d)
        w_comb = (weights.reshape(-1).astype(xf_l.dtype)
                  * keep.astype(xf_l.dtype))
        return jnp.sum((per_slot * w_comb[:, None]).reshape(t_loc, k, d),
                       axis=1)

    spec_tok = P(tok_axes, None)
    spec_w = P("model", None, None)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec_tok, spec_tok, spec_w, spec_w, spec_w),
                         out_specs=spec_tok, check_vma=False)(
        xf, logits, w_gate, w_up, w_down)


def moe_alltoall_exact_c_dev(logits, mesh, top_k, capacity=None,
                             overflow="global",
                             local_capacity_factor=2.0):
    """Phase-1 of two-phase wire-buffer sizing: the exact max number of
    kept (token, slot) pairs any (source device, destination shard)
    slab carries, rounded up to a multiple of 8 (lane alignment) with a
    floor of 8, never above the static safety bound.

    Runs the SAME routing + keep math as :func:`moe_alltoall_block`
    (``_route_keep``) over the logits only — no activations move — and
    reduces the per-(device, dest) kept counts to one host integer.
    The result is a static shape, so this MUST run outside jit: call it
    on concrete logits (e.g. the previous step's, or a profiling
    batch), then pass the result as ``c_dev`` with ``exact_c_dev=True``.
    Outputs are elementwise identical for any ``c_dev`` >= the true
    max, so resizing between steps never changes numerics.

    ``overflow="local"`` already sizes its buffer from its own drop
    rule — the legacy formula IS exact there and is returned directly.
    """
    import math

    from jax.sharding import PartitionSpec as P

    from .sharding import dp_axes

    if isinstance(logits, jax.core.Tracer):
        raise ValueError(
            "moe_alltoall_exact_c_dev must run outside jit: its result "
            "becomes a static wire-buffer shape (size on concrete "
            "logits, then pass c_dev into the jitted step)")
    n_model = int(mesh.shape["model"])
    e = int(logits.shape[-1])
    assert e % n_model == 0, (e, n_model)
    e_loc = e // n_model
    dp_names = dp_axes(mesh)
    tok_axes = tuple(dp_names) + ("model",)
    n_dp = int(math.prod(int(mesh.shape[a]) for a in dp_names)) \
        if dp_names else 1
    n_tok_dev = n_dp * n_model
    t_loc = int(logits.shape[0]) // n_tok_dev
    k = int(top_k)
    if overflow == "local":
        return math.ceil(t_loc * k * float(local_capacity_factor) / n_model)
    if overflow != "global":
        raise ValueError(f"unknown overflow mode {overflow!r} "
                         "(expected 'global' or 'local')")
    if capacity is None:
        raise ValueError(
            "moe_alltoall_exact_c_dev(overflow='global') needs the "
            "global per-expert `capacity` used by the scatter path")
    bound = min(t_loc * k, e_loc * int(capacity))

    def count(logits_l):
        _, _, dest, _, keep = _route_keep(
            logits_l, k, e, e_loc, n_model, tok_axes, mesh, n_tok_dev,
            capacity, "global")
        kept = jnp.zeros((n_model,), jnp.int32).at[dest].add(
            keep.astype(jnp.int32))
        return kept[None, :]                 # (1, n_model) per device

    spec_tok = P(tok_axes, None)
    counts = jax.shard_map(count, mesh=mesh, in_specs=(spec_tok,),
                           out_specs=spec_tok, check_vma=False)(logits)
    max_kept = int(jax.device_get(counts).max())
    return min(bound, max(8, -(-max_kept // 8) * 8))


def _pod_mean(x32, compress: bool):
    """fp32 mean over the 'pod' axis, optionally int8-compressed for the
    slow DCN link — the shared cross-pod hop of ``grad_sync`` and
    ``cross_pod_allreduce``."""
    if compress:
        from repro.optim.compress import dequantize_int8, quantize_int8
        q, s = quantize_int8(x32)
        return jax.lax.pmean(dequantize_int8(q, s), "pod")
    return jax.lax.pmean(x32, "pod")


def grad_sync(mesh, grads, int8_cross_pod: bool = False):
    """Hierarchical gradient mean over the data-parallel axes.

    In-pod (``data``) reduction runs in fp32; the cross-pod hop (the slow
    DCN link) optionally quantizes its summand to int8 with per-tensor
    scales (``optim.compress``) before reducing — via the same
    :func:`_pod_mean` body that backs :func:`cross_pod_allreduce`.
    Tensor-parallel (``model``) gradients are already replicated and
    untouched.
    """
    if mesh is None or all(int(s) == 1 for s in mesh.shape.values()):
        return grads
    from jax.sharding import PartitionSpec as P

    from .sharding import dp_axes

    dp = dp_axes(mesh)
    if not dp:
        return grads
    in_pod = tuple(a for a in dp if a != "pod")

    def body(g):
        def one(x):
            x32 = x.astype(jnp.float32)
            if in_pod:
                x32 = jax.lax.pmean(x32, in_pod)
            if "pod" in dp:
                x32 = _pod_mean(x32, int8_cross_pod)
            return x32.astype(x.dtype)

        return jax.tree_util.tree_map(one, g)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P())(grads)


def cross_pod_allreduce(mesh, x, compress: bool = False, in_spec=None):
    """Mean-allreduce of one tensor over the 'pod' axis.

    The standalone form of ``grad_sync``'s cross-pod hop (same
    :func:`_pod_mean` body, so the two cannot drift): use it to average
    metrics, EMA shadows, or other per-pod state that does not ride the
    gradient pytree.  ``in_spec`` is the tensor's PartitionSpec (default
    replicated); the output keeps the same spec, with the value averaged
    across pods.  Identity on meshes without a 'pod' axis (or pod=1).
    """
    if mesh is None or "pod" not in mesh.axis_names \
            or int(mesh.shape["pod"]) == 1:
        return x
    from jax.sharding import PartitionSpec as P


    spec = in_spec if in_spec is not None else P()

    def body(xl):
        return _pod_mean(xl.astype(jnp.float32), compress).astype(x.dtype)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(x)
