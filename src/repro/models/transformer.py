"""Decoder-only LM (dense + MoE) with scan-over-layers, GQA, RoPE
variants, sliding-window/global mixes, KV-cache decode, and train/serve
steps.  Covers the five assigned LM architectures (moonshot, qwen3-moe,
phi4-mini, gemma3, chatglm3) from a single config dataclass.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro import dist
from repro.dist.sharding import dp_axes

from .layers import (AttnConfig, attention, attn_qkv, decode_attention_block,
                     embed, init_attn, init_embedding, init_mlp, mlp_block,
                     rms_norm, self_attention_block, unembed)
from .moe import MoEConfig, init_moe, moe_block


def _logits_spec(mesh):
    """(B, S, V): batch over DP, sequence over 'model' (SP)."""
    return P(dp_axes(mesh), "model", None)


def _flat_spec(mesh):
    """Flattened (B*S, d) token activations: all DP axes + 'model'."""
    axes = tuple(dp_axes(mesh)) + ("model",)
    return P(axes, None)


def _flat_vec_spec(mesh):
    axes = tuple(dp_axes(mesh)) + ("model",)
    return P(axes)


def _act_spec(mesh):
    """Training activations (B, S, d): DP on batch + sequence parallelism
    on 'model' — keeps the per-layer attention score matrix at
    (B/dp, H, S/model, S), which is what fits a 4k x 4k context in HBM.
    XLA inserts the all-gathers of K/V that SP implies."""
    return P(dp_axes(mesh), "model", None)

Param = dict


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    qkv_bias: bool = False
    # cycle of per-layer windows; 0 = global attention.  gemma3 uses
    # (512,)*5 + (0,) i.e. 5 local : 1 global.
    window_pattern: Tuple[int, ...] = (0,)
    moe: Optional[MoEConfig] = None
    tie_embeddings: bool = True
    embed_scale: bool = False          # gemma multiplies embeddings by sqrt(d)
    aux_loss_weight: float = 0.01
    remat: str = "layer"               # layer | none (activation ckpt)
    loss_chunks: int = 16              # vocab chunks for fused CE (0=full)
    # decode attention over the S-sharded KV cache:
    # "gather" = let the partitioner all-gather K/V (baseline);
    # "splitk" = shard_map flash-decoding: local partial softmax per KV
    #            shard + tiny (B,H,D) psum combine (§Perf)
    decode_attn: str = "gather"
    dtype: str = "bfloat16"

    @property
    def attn(self) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_kv, self.d_head,
                          self.rope_theta, self.rope_fraction, self.qkv_bias)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def layer_windows(self) -> np.ndarray:
        pat = self.window_pattern
        return np.asarray([pat[i % len(pat)] for i in range(self.n_layers)],
                          dtype=np.int32)

    def n_params(self) -> int:
        """Total parameter count (for 6ND model-flops accounting)."""
        a = self.attn
        attn = self.d_model * (a.n_heads + 2 * a.n_kv) * a.d_head \
            + a.n_heads * a.d_head * self.d_model
        if self.moe:
            m = self.moe
            ffn = m.n_experts * 3 * self.d_model * m.d_expert \
                + self.d_model * m.n_experts \
                + (3 * self.d_model * m.n_shared * m.d_expert if m.n_shared
                   else 0)
        else:
            ffn = 3 * self.d_model * self.d_ff
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * (attn + ffn + 2 * self.d_model) + emb

    def n_active_params(self) -> int:
        """Active parameters per token (MoE top-k only)."""
        if not self.moe:
            return self.n_params()
        a = self.attn
        attn = self.d_model * (a.n_heads + 2 * a.n_kv) * a.d_head \
            + a.n_heads * a.d_head * self.d_model
        m = self.moe
        ffn = m.top_k * 3 * self.d_model * m.d_expert \
            + self.d_model * m.n_experts \
            + (3 * self.d_model * m.n_shared * m.d_expert if m.n_shared else 0)
        emb = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return self.n_layers * (attn + ffn + 2 * self.d_model) + emb


# --------------------------------------------------------------------- init
def init_layer(key, cfg: LMConfig) -> Param:
    ka, km, k1, k2 = jax.random.split(key, 4)
    dt = cfg.jdtype
    p = {
        "attn": init_attn(ka, cfg.attn, dt),
        "ln_attn": jnp.zeros((cfg.d_model,), dt),
        "ln_mlp": jnp.zeros((cfg.d_model,), dt),
    }
    if cfg.moe:
        p["moe"] = init_moe(km, cfg.d_model, cfg.moe, dt)
    else:
        p["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, gated=True, dtype=dt)
    return p


def init_params(key, cfg: LMConfig) -> Param:
    ke, kl, ko = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    # stacked layer params: every leaf gains a leading (n_layers,) dim
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    p = {
        "embed": init_embedding(ke, cfg.vocab, cfg.d_model, cfg.jdtype),
        "layers": layers,
        "ln_f": jnp.zeros((cfg.d_model,), cfg.jdtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = init_embedding(ko, cfg.vocab, cfg.d_model, cfg.jdtype)
    return p


# ------------------------------------------------------------------ forward
def _layer_fwd(cfg: LMConfig, x, positions, lp, window):
    h = rms_norm(x, lp["ln_attn"])
    b, s, _ = h.shape
    q, k, v = attn_qkv(lp["attn"], h, cfg.attn, positions)
    o = _windowed_attention(q, k, v, positions, window)
    x = x + o.reshape(b, s, -1) @ lp["attn"]["wo"]
    h = rms_norm(x, lp["ln_mlp"])
    if cfg.moe:
        y, aux = moe_block(lp["moe"], h, cfg.moe)
    else:
        y, aux = mlp_block(lp["mlp"], h), jnp.float32(0.0)
    return x + y, aux


def _score_spec(mesh):
    """(B, H, Sq, Sk) attention scores: batch over DP, q-seq over 'model'
    (matches the SP activation layout).  Without this pin, the partitioner
    hits a propagation cliff on the 512-chip mesh and materializes the
    full score tensor ("involuntary full rematerialization", 1 TiB/dev
    measured on qwen3 multi-pod)."""
    return P(dp_axes(mesh), None, "model", None)


def _windowed_attention(q, k, v, positions, window):
    """Causal attention with a traced per-layer window (0 = global)."""
    from .layers import _repeat_kv, NEG_INF
    b, sq, hq, d = q.shape
    n_rep = hq // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = dist.constrain(logits, _score_spec)
    dq = positions[:, :, None]
    dk = positions[:, None, :]
    mask = dk <= dq
    win_mask = jnp.logical_or(window <= 0, dq - dk < window)
    mask = jnp.logical_and(mask, win_mask)
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def forward_features(params: Param, tokens: jnp.ndarray, cfg: LMConfig):
    """tokens (B, S) -> final hidden states (B, S, d), plus MoE aux."""
    b, s = tokens.shape
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(carry, layer_in):
        x, aux = carry
        lp, window = layer_in
        x = dist.constrain(x, _act_spec)
        x, a = _layer_fwd(cfg, x, positions, lp, window)
        return (x, aux + a), None

    if cfg.remat == "layer":
        body = jax.checkpoint(body)
    windows = jnp.asarray(cfg.layer_windows())
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                               (params["layers"], windows))
    x = rms_norm(x, params["ln_f"])
    return x, aux


def forward(params: Param, tokens: jnp.ndarray, cfg: LMConfig):
    """tokens (B, S) -> logits (B, S, V) in activation dtype, + MoE aux."""
    x, aux = forward_features(params, tokens, cfg)
    out_emb = params.get("unembed", params["embed"])
    logits = unembed(out_emb, x)
    logits = dist.constrain(logits, _logits_spec)
    return logits, aux


def chunked_ce(x: jnp.ndarray, table: jnp.ndarray, labels: jnp.ndarray,
               n_chunks: int) -> jnp.ndarray:
    """Fused unembed + cross-entropy, streamed over vocab chunks.

    Never materializes the (T, V) logits: each scan step computes one
    (T, V/n_chunks) block, folds it into a running (max, sumexp) pair and
    picks out the label logit.  jax.checkpoint on the chunk body keeps the
    backward at one recomputed block at a time.  x: (T, d); table: (V, d);
    labels: (T,) -> per-token nll (T,) fp32.
    """
    t, d = x.shape
    v = table.shape[0]
    vc = v // n_chunks
    assert vc * n_chunks == v, (v, n_chunks)
    chunks = table.reshape(n_chunks, vc, d)

    def body(carry, inp):
        m, s, ll = carry
        i, tb = inp
        lg = jnp.einsum("td,vd->tv", x, tb,
                        preferred_element_type=jnp.float32)  # (T, Vc)
        cm = jnp.max(lg, axis=-1)
        nm = jnp.maximum(m, cm)
        s = s * jnp.exp(m - nm) + jnp.sum(jnp.exp(lg - nm[:, None]),
                                          axis=-1)
        lo = i * vc
        in_chunk = (labels >= lo) & (labels < lo + vc)
        idx = jnp.clip(labels - lo, 0, vc - 1)
        lbl = jnp.take_along_axis(lg, idx[:, None], axis=1)[:, 0]
        ll = jnp.where(in_chunk, lbl, ll)
        return (nm, s, ll), None

    init = (jnp.full((t,), -jnp.inf, jnp.float32),
            jnp.zeros((t,), jnp.float32), jnp.zeros((t,), jnp.float32))
    (m, s, ll), _ = jax.lax.scan(
        jax.checkpoint(body), init,
        (jnp.arange(n_chunks, dtype=jnp.int32), chunks))
    return m + jnp.log(jnp.maximum(s, 1e-30)) - ll


def lm_loss(params: Param, batch: dict, cfg: LMConfig):
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    if cfg.loss_chunks and cfg.vocab % cfg.loss_chunks == 0:
        x, aux = forward_features(params, batch["tokens"], cfg)
        b, s_, d = x.shape
        out_emb = params.get("unembed", params["embed"])
        xf = dist.constrain(x.reshape(b * s_, d), _flat_spec)
        lf = dist.constrain(labels.reshape(-1), _flat_vec_spec)
        nll = chunked_ce(xf, out_emb["table"], lf, cfg.loss_chunks)
        nll = nll.reshape(b, s_)
    else:
        logits, aux = forward(params, batch["tokens"], cfg)
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
        nll = lse - ll
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + cfg.aux_loss_weight * aux, {"loss": loss, "aux": aux}


# ------------------------------------------------------------------- decode
def init_cache(cfg: LMConfig, batch: int, max_len: int,
               window_bounded: bool = False):
    """KV cache pytree.  ``window_bounded=True`` allocates only
    ``window`` slots for sliding-window layers (the §Perf-optimized
    layout); the baseline allocates ``max_len`` for every layer."""
    windows = cfg.layer_windows()
    if window_bounded:
        lens = np.asarray([w if w > 0 else max_len for w in windows])
        s = int(lens.max())   # scan needs uniform shapes; bound by max
    else:
        s = max_len
    shape = (cfg.n_layers, batch, s, cfg.n_kv, cfg.d_head)
    dt = cfg.jdtype
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "len": jnp.zeros((batch,), jnp.int32)}


def prefill(params: Param, tokens: jnp.ndarray, cfg: LMConfig,
            max_len: Optional[int] = None):
    """Run the prompt, return last-position logits + populated cache."""
    b, s = tokens.shape
    max_len = max_len or s
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def cache_spec(mesh):
        # (B, S, kv, dh) per layer -> batch over DP, seq over 'model'
        return P(dp_axes(mesh), "model", None, None)

    def body(x, layer_in):
        lp, window = layer_in
        x = dist.constrain(x, _act_spec)
        h = rms_norm(x, lp["ln_attn"])
        q, k, v = attn_qkv(lp["attn"], h, cfg.attn, positions)
        o = _windowed_attention(q, k, v, positions, window)
        x = x + o.reshape(b, s, -1) @ lp["attn"]["wo"]
        h = rms_norm(x, lp["ln_mlp"])
        if cfg.moe:
            y, _ = moe_block(lp["moe"], h, cfg.moe)
        else:
            y = mlp_block(lp["mlp"], h)
        x = x + y
        pad = max_len - s
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kc = dist.constrain(kc, cache_spec)
        vc = dist.constrain(vc, cache_spec)
        return x, (kc, vc)

    windows = jnp.asarray(cfg.layer_windows())
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], windows))
    x = rms_norm(x, params["ln_f"])
    out_emb = params.get("unembed", params["embed"])
    logits = unembed(out_emb, x[:, -1:, :])
    cache = {"k": ks, "v": vs,
             "len": jnp.full((b,), s, jnp.int32)}
    return logits, cache


def decode_step(params: Param, cache: dict, tokens: jnp.ndarray,
                cfg: LMConfig):
    """One decode step: tokens (B, 1) -> logits (B, 1, V), new cache."""
    b = tokens.shape[0]
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    lens = cache["len"]

    def body(x, layer_in):
        lp, window, kc, vc = layer_in
        h = rms_norm(x, lp["ln_attn"])
        mesh = dist.get_mesh()
        if (cfg.decode_attn == "splitk" and mesh is not None
                and "model" in mesh.axis_names
                and kc.shape[1] % mesh.shape["model"] == 0):
            o, nk, nv = _decode_attn_splitk(lp["attn"], h, cfg, kc, vc,
                                            lens, window, mesh)
        else:
            o, nk, nv = _decode_attn(lp["attn"], h, cfg, kc, vc, lens,
                                     window)
        x = x + o
        h = rms_norm(x, lp["ln_mlp"])
        if cfg.moe:
            y, _ = moe_block(lp["moe"], h, cfg.moe)
        else:
            y = mlp_block(lp["mlp"], h)
        return x + y, (nk, nv)

    windows = jnp.asarray(cfg.layer_windows())
    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], windows, cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_f"])
    out_emb = params.get("unembed", params["embed"])
    logits = unembed(out_emb, x)
    new_cache = {"k": ks, "v": vs, "len": lens + 1}
    return logits, new_cache


def _decode_attn(p, x, cfg: LMConfig, k_cache, v_cache, lens, window):
    from .layers import NEG_INF, _repeat_kv
    b = x.shape[0]
    positions = lens[:, None]
    q, k_new, v_new = attn_qkv(p, x, cfg.attn, positions)
    s_max = k_cache.shape[1]
    write_idx = jnp.minimum(lens, s_max - 1)
    k_cache = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
        c, n, (i, 0, 0)))(k_cache, k_new, write_idx)
    v_cache = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
        c, n, (i, 0, 0)))(v_cache, v_new, write_idx)
    k_pos = jnp.broadcast_to(jnp.arange(s_max, dtype=jnp.int32), (b, s_max))
    valid = k_pos <= lens[:, None]
    n_rep = cfg.n_heads // cfg.n_kv
    kk = _repeat_kv(k_cache, n_rep)
    vv = _repeat_kv(v_cache, n_rep)
    scale = 1.0 / np.sqrt(cfg.d_head)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    win_ok = jnp.logical_or(window <= 0,
                            positions[:, :, None] - k_pos[:, None, :] < window)
    mask = jnp.logical_and(valid[:, None, :], win_ok)
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    out = o.reshape(b, 1, -1) @ p["wo"]
    return out, k_cache, v_cache


def _decode_attn_splitk(p, x, cfg: LMConfig, k_cache, v_cache, lens,
                        window, mesh):
    """Flash-decoding over a sequence-sharded KV cache.

    The baseline lets the SPMD partitioner all-gather the full K/V cache
    per layer per token (~16 GB/step for gemma3 long_500k, measured).
    Here each device attends over its LOCAL cache shard with running
    (max, sumexp, acc) statistics and the combine is a psum over
    (B, H, D) — bytes per layer drop from O(S*kv*dh) to O(H*dh).
    The cache write happens only on the shard that owns position
    ``lens`` (no cross-shard traffic).
    """
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    positions = lens[:, None]
    q, k_new, v_new = attn_qkv(p, x, cfg.attn, positions)   # (B,1,H,dh)...
    n_rep = cfg.n_heads // cfg.n_kv
    s_max = k_cache.shape[1]
    n_sh = mesh.shape["model"]
    s_loc = s_max // n_sh
    dpx = dp_axes(mesh)
    bax = dpx if b % max(dist.axis_size(mesh, dpx), 1) == 0 else None

    def local(q, k_new, v_new, kc, vc, lens_):
        shard = jax.lax.axis_index("model")
        base = (shard * s_loc).astype(jnp.int32)
        idx = lens_ - base                       # (B,)
        own = (idx >= 0) & (idx < s_loc)
        iw = jnp.clip(idx, 0, s_loc - 1)

        def upd(c, n, i, o):
            # write exactly one row: select between the new KV row and
            # the row already there (a full-cache `where` would rewrite
            # the whole shard every layer — measured 190 GB/step)
            cur = jax.lax.dynamic_slice(c, (i, 0, 0), n.shape)
            val = jnp.where(o, n, cur)
            return jax.lax.dynamic_update_slice(c, val, (i, 0, 0))

        kc = jax.vmap(upd)(kc, k_new, iw, own)
        vc = jax.vmap(upd)(vc, v_new, iw, own)
        k_pos = base + jnp.arange(s_loc, dtype=jnp.int32)   # (s_loc,)
        valid = k_pos[None, :] <= lens_[:, None]            # (B, s_loc)
        win_ok = jnp.logical_or(
            window <= 0, lens_[:, None] - k_pos[None, :] < window)
        mask = valid & win_ok
        kk = _windowed_repeat(kc, n_rep)
        vv = _windowed_repeat(vc, n_rep)
        scale = 1.0 / np.sqrt(cfg.d_head)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[:, None, None, :], logits,
                           jnp.float32(-1e30))
        m_loc = jnp.max(logits, axis=-1)                    # (B,H,1)
        m_glob = jax.lax.pmax(m_loc, "model")
        pexp = jnp.exp(logits - m_glob[..., None])
        l_loc = jnp.sum(pexp, axis=-1)                      # (B,H,1)
        acc = jnp.einsum("bhqk,bkhd->bqhd", pexp.astype(vv.dtype), vv)
        l = jax.lax.psum(l_loc, "model")
        acc = jax.lax.psum(acc.astype(jnp.float32), "model")
        o = (acc / jnp.maximum(
            l.transpose(0, 2, 1)[..., None], 1e-30)).astype(x.dtype)
        return o, kc, vc

    cache_spec = P(bax, "model", None, None)
    small_spec = P(bax, None, None, None)
    o, nk, nv = jax.shard_map(
        local, mesh=mesh,
        in_specs=(small_spec, small_spec, small_spec, cache_spec,
                  cache_spec, P(bax)),
        out_specs=(small_spec, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, k_cache, v_cache, lens)
    out = o.reshape(b, 1, -1) @ p["wo"]
    return out, nk, nv


def _windowed_repeat(k, n_rep):
    from .layers import _repeat_kv
    return _repeat_kv(k, n_rep)
