"""Shared model layers: norms, RoPE, activations, MLPs, GQA attention.

Everything is a pure function over explicit parameter dicts; activations
carry logical sharding annotations via :func:`repro.launch.sharding.shard`
(no-ops outside a rules context).  Attention supports full-causal and
sliding-window (banded) masks, encoder (bidirectional) use, and single-token
decode against a (possibly ring-buffered) KV cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.launch.sharding import resolves, shard



def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "sq_relu":                       # Nemotron-4 squared ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: (..., S, H, hd), positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp(p: dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    act = activation(cfg.act)
    if cfg.act == "silu":                      # gated (SwiGLU-style)
        h = act(x @ p["wg"]) * (x @ p["wu"])
    else:
        h = act(x @ p["wi"])
    # keep the token dim sharded when the arch cannot head-shard (llava,
    # starcoder2): otherwise the gather replicates MLP compute 16×
    seq_ax = "seq" if resolves(cfg.n_heads, "heads") else "act_seq"
    h = shard(h, "batch", seq_ax, "mlp")
    return h @ p["wd"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, KV, hd) → (B, S, KV*groups, hd) for GQA."""
    if groups == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, hd)
                            ).reshape(b, s, kv * groups, hd)


def qkv_proj(p: dict, cfg: ArchConfig, x: jax.Array, positions,
             use_rope: bool = True) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # when heads cannot take the model axis (llava 56H, starcoder2 24H)
    # keep the *sequence* sharded instead — otherwise the projections
    # replicate over the whole model axis (16× compute per device)
    q_seq = "seq" if resolves(q.shape[2], "heads") else "act_seq"
    kv_seq_ax = "seq" if resolves(k.shape[2], "kv_heads") else "act_seq"
    q = shard(q, "batch", q_seq, "heads", "head_dim")
    k = shard(k, "batch", kv_seq_ax, "kv_heads", "head_dim")
    v = shard(v, "batch", kv_seq_ax, "kv_heads", "head_dim")
    return q, k, v


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool, window: int = 0,
           q_offset: int = 0) -> jax.Array:
    """Reference attention (B, Sq, H, hd) × (B, Sk, KV, hd) → (B, Sq, H, hd).

    ``window`` > 0 applies a sliding-window band; ``q_offset`` is the
    absolute position of q[0] relative to k[0] (for chunked prefill).
    """
    groups = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    # big intermediate: shard heads over 'model' (or q-seq when heads are
    # not divisible — llava 56H, starcoder2 24H; 'used' tracking picks one)
    logits = shard(logits, "batch", "heads", "seq_model", None)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    seq_ax = "seq" if resolves(q.shape[2], "heads") else "act_seq"
    return shard(out, "batch", seq_ax, "heads", "head_dim")


CHUNK_Q_THRESHOLD = 16_384
# §Perf iteration: 2048-row chunks halve the fp32 chunk-logits working set
# vs 4096 (llava prefill 16.3 → 12.8 GB/dev, fits HBM); 1024 gave <2 %
# more (KV emission dominates beyond this) — diminishing returns reached.
CHUNK_Q = 2_048


def attend_pallas(q, k, v, *, causal: bool, window: int = 0) -> jax.Array:
    """Route through the Pallas flash-attention kernel (kernels/).

    Layout adapters only: (B,S,H,hd) ↔ the kernel's (B,H,S,hd)/(B,KV,S,hd).
    Interpret-mode on CPU; Mosaic on TPU.
    """
    from repro.kernels import ops
    bq = min(128, q.shape[1])
    out = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        block_q=bq, block_k=bq)
    return out.transpose(0, 2, 1, 3)


def attend_auto(q, k, v, *, causal: bool, window: int = 0,
                unroll: bool = False, impl: str = "ref") -> jax.Array:
    """attend(), q-chunked above 16k tokens so the (Sq, Sk) logits never
    materialize (≈15 GB/device for llava at 32k otherwise).

    ``unroll=True`` expands the chunk loop in Python — used by the roofline
    delta method, where ``lax.scan`` bodies would be cost-counted once.
    ``impl="pallas"`` dispatches to the flash-attention kernel.
    """
    if impl == "pallas":
        return attend_pallas(q, k, v, causal=causal, window=window)
    b, s, h, hd = q.shape
    if s < CHUNK_Q_THRESHOLD:
        return attend(q, k, v, causal=causal, window=window)
    # pad queries up to a CHUNK_Q multiple (llava's 32768+2880 image
    # prefix): padded rows attend like ordinary tokens and are dropped —
    # keeping chunks 4096-aligned so the seq_model sharding divides
    s_pad = -(-s // CHUNK_Q) * CHUNK_Q
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    nc = s_pad // CHUNK_Q
    cq = CHUNK_Q
    if unroll:
        outs = [attend(q[:, i * cq:(i + 1) * cq], k, v,
                       causal=causal, window=window, q_offset=i * cq)
                for i in range(nc)]
        return jnp.concatenate(outs, axis=1)[:, :s]
    qc = jnp.moveaxis(q.reshape(b, nc, cq, h, hd), 1, 0)

    def body(_, xs):
        off, qi = xs
        return None, attend(qi, k, v, causal=causal, window=window,
                            q_offset=off)

    _, outs = jax.lax.scan(body, None, (jnp.arange(nc) * cq, qc))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s_pad, h, hd)
    return out[:, :s]


def attention_block(p: dict, cfg: ArchConfig, x: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    positions: Optional[jax.Array] = None,
                    use_rope: bool = True) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = qkv_proj(p, cfg, x, positions, use_rope)
    w = cfg.sliding_window if window is None else window
    out = attend_auto(q, k, v, causal=causal, window=w,
                      unroll=cfg.unroll_layers, impl=cfg.attn_impl)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# KV cache (contiguous or ring-buffered for sliding windows)
# ---------------------------------------------------------------------------

def cache_width(cfg: ArchConfig, max_seq: int) -> int:
    """Sliding-window archs only ever hold `window` keys (sub-linear at
    500k context); full attention holds the whole sequence."""
    return min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq


def init_kv_cache(cfg: ArchConfig, n_layers: int, batch: int, max_seq: int,
                  dtype) -> dict:
    w = cache_width(cfg, max_seq)
    shape = (n_layers, batch, w, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(cache_k, cache_v, layer: int, k: jax.Array, v: jax.Array,
                 pos: jax.Array, window: int) -> tuple[jax.Array, jax.Array]:
    """Write one token's K/V at ``pos`` (ring-buffered if window > 0)."""
    w = cache_k.shape[2]
    slot = pos % w if window else jnp.minimum(pos, w - 1)
    ck = jax.lax.dynamic_update_slice(
        cache_k[layer], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache_v[layer], v, (0, slot, 0, 0))
    return cache_k.at[layer].set(ck), cache_v.at[layer].set(cv)


def decode_attend(q: jax.Array, ck: jax.Array, cv: jax.Array, *,
                  pos: jax.Array, window: int) -> jax.Array:
    """Single-token attention over the cache.

    q: (B, 1, H, hd); ck/cv: (B, W, KV, hd); ``pos`` is the absolute
    position of the new token (its K/V already written to the cache).

    GQA is computed with the query heads grouped per KV head — the KV
    cache is never re-materialized ``groups``× (that repeat dominated
    decode temps for nemotron's 12-way GQA at 32k context).
    """
    b, one, h, hd = q.shape
    kv = ck.shape[2]
    groups = h // kv
    qg = q.reshape(b, kv, groups, hd)        # query heads per KV head
    scale = hd ** -0.5
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, ck).astype(
        jnp.float32) * scale                  # (B, KV, G, W)
    w = ck.shape[1]
    slots = jnp.arange(w)
    if window:
        # ring buffer: slot s holds absolute position p_s = pos−((pos−s)%w),
        # automatically causal and within the window; it is valid iff it
        # has been written at all, i.e. p_s ≥ 0.
        valid = (pos - slots) % w <= pos
    else:
        valid = slots <= pos
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)
    logits = shard(logits, "batch", "kv_heads", None, "kv_seq")
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, cv)    # (B, KV, G, hd)
    return out.reshape(b, 1, h, hd)


# ---------------------------------------------------------------------------
# §Perf: shard_map flash-decode (beyond-paper optimization)
# ---------------------------------------------------------------------------

def decode_update_attend_sharded(cfg: ArchConfig, q, k_new, v_new, ck, cv,
                                 pos, window: int):
    """Cache update + single-token attention with the cache *sequence*
    dimension explicitly sharded over the ``model`` axis.

    The GSPMD baseline re-gathers the whole per-layer cache at every
    ``dynamic_update_slice`` (the write slot crosses shard boundaries) —
    the "involuntary full rematerialization" XLA warns about, ≈0.2 GB per
    layer per step.  Here each model shard owns a contiguous cache slice:
    the owner writes the new K/V locally, every shard computes a partial
    online-softmax (flash-decode), and the combine is a pmax/psum of
    (B, KV, G)-sized partials — bytes per step drop from O(cache) to
    O(q).

    q: (B, 1, H, hd); k_new/v_new: (B, 1, KV, hd); ck/cv: (B, W, KV, hd).
    Returns (out (B, 1, H, hd), ck, cv).
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import current_mesh

    mesh = current_mesh()
    b, _, h, hd = q.shape
    kv = ck.shape[2]
    groups = h // kv
    w = ck.shape[1]
    batch_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_ax = batch_ax if b % _prod(mesh.shape[a] for a in batch_ax) == 0 \
        else ()
    n_model = mesh.shape["model"]
    seq_ax = "model" if w % n_model == 0 else None
    bspec = batch_ax or None

    qs = P(bspec, None, None, None)
    kvnew = P(bspec, None, None, None)
    cache_spec = P(bspec, seq_ax, None, None)

    def body(q_l, kn_l, vn_l, ck_l, cv_l):
        w_loc = ck_l.shape[1]
        if seq_ax:
            my_lo = jax.lax.axis_index("model") * w_loc
        else:
            my_lo = 0
        slot_g = pos % w if window else jnp.minimum(pos, w - 1)
        slot_l = jnp.clip(slot_g - my_lo, 0, w_loc - 1)
        mine = (slot_g >= my_lo) & (slot_g < my_lo + w_loc)
        ck_new = jax.lax.dynamic_update_slice(ck_l, kn_l, (0, slot_l, 0, 0))
        cv_new = jax.lax.dynamic_update_slice(cv_l, vn_l, (0, slot_l, 0, 0))
        ck_l = jnp.where(mine, ck_new, ck_l)
        cv_l = jnp.where(mine, cv_new, cv_l)

        bl = q_l.shape[0]
        qg = q_l.reshape(bl, kv, groups, hd)
        logits = jnp.einsum("bkgd,bskd->bkgs", qg, ck_l).astype(
            jnp.float32) * hd ** -0.5                  # (B, KV, G, W_loc)
        slots = my_lo + jnp.arange(w_loc)
        if window:
            valid = (pos - slots) % w <= pos
        else:
            valid = slots <= pos
        logits = jnp.where(valid[None, None, None, :], logits, -1e30)
        m_loc = logits.max(axis=-1)                    # (B, KV, G)
        if seq_ax:
            m = jax.lax.pmax(m_loc, "model")
        else:
            m = m_loc
        p_ = jnp.exp(logits - m[..., None])
        p_ = jnp.where(valid[None, None, None, :], p_, 0.0)
        l_loc = p_.sum(axis=-1)
        o_loc = jnp.einsum("bkgs,bskd->bkgd", p_.astype(q_l.dtype), cv_l)
        if seq_ax:
            l = jax.lax.psum(l_loc, "model")
            o = jax.lax.psum(o_loc.astype(jnp.float32), "model")
        else:
            l, o = l_loc, o_loc.astype(jnp.float32)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(bl, 1, h, hd), ck_l, cv_l

    out, ck, cv = jax.shard_map(
        body, mesh=mesh,
        in_specs=(qs, kvnew, kvnew, cache_spec, cache_spec),
        out_specs=(qs, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, ck, cv)
    return out, ck, cv


def _prod(it):
    out = 1
    for x in it:
        out *= x
    return out
