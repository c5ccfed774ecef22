"""Transformer building blocks: norms, RoPE, GQA attention, MLP, MoE.

Everything is a pure function over explicit param trees (leaves created via
:class:`repro.models.params.PLeaf` so dim specs travel with the arrays).

Attention supports the union of the assigned architectures' needs:
  * GQA with arbitrary q/kv ratios (the einsums keep the kv-head dim explicit
    so tensor-parallel sharding applies to it),
  * qk-norm (qwen3, gemma3), RoPE with per-layer theta (gemma3 global layers),
  * causal / sliding-window / bidirectional / cross masks,
  * prefill (returns a KV cache) and single-token decode (ring buffer for
    sliding-window layers → O(window) memory at 500k-token contexts).

MoE uses sort-based grouped-GEMM dispatch with a capacity factor (drop policy)
— the production TPU shape ``(E, C, D) · (E, D, F)`` with the expert dim
sharded over the model axis (EP).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.params import PLeaf, dense_init

NEG_INF = -2.0e38


def _c(rules, x, dims):
    return x if rules is None else rules.constraint(x, dims)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": PLeaf(jnp.ones((d,), dtype), ((None,),))}


def rms_norm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype) -> dict:
    return {
        "scale": PLeaf(jnp.ones((d,), dtype), ((None,),)),
        "bias": PLeaf(jnp.zeros((d,), dtype), ((None,),)),
    }


def layer_norm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype, cross: bool = False) -> dict:
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": PLeaf(dense_init(ks[0], (d, h, hd), dtype),
                    (("fsdp",), ("tp",), (None, "tp"))),
        "wk": PLeaf(dense_init(ks[1], (d, hk, hd), dtype),
                    (("fsdp",), ("tp",), (None, "tp"))),
        "wv": PLeaf(dense_init(ks[2], (d, hk, hd), dtype),
                    (("fsdp",), ("tp",), (None, "tp"))),
        "wo": PLeaf(dense_init(ks[3], (h, hd, d), dtype,
                               scale=1.0 / math.sqrt(h * hd)),
                    (("tp",), (None, "tp"), ("fsdp",))),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _mask_bias(mode: str, mask_kind: str, q_len: int, kv_len: int,
               q_pos: jax.Array, kv_pos: jax.Array,
               kv_valid: Optional[jax.Array], window: Optional[int]):
    """(q_len, kv_len) additive bias (or (B, q, kv) if kv_valid is batched)."""
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    if mask_kind == "causal":
        ok = kp <= qp
    elif mask_kind == "sliding":
        ok = (kp <= qp) & (kp > qp - window)
    elif mask_kind in ("bidir", "cross"):
        ok = jnp.ones((q_len, kv_len), bool)
    else:
        raise ValueError(mask_kind)
    bias = jnp.where(ok, 0.0, NEG_INF)
    if kv_valid is not None:
        bias = bias[None] + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, :]
    return bias


def _sdpa(q, k, v, bias, rules, g: int):
    """q: (B,Q,H,D); k,v: (B,K,Hk,D), repeated to H heads (GQA).

    The kv-repeat (a fused broadcast) keeps the head dim at H everywhere so
    tensor-parallel head sharding survives the contraction — reshaping to
    (Hk, G) would split the sharded dim and trigger GSPMD re-replication.
    """
    hd = q.shape[-1]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    if bias.ndim == 2:
        scores = scores + bias[None, None]
    else:
        scores = scores + bias[:, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


_BLOCK_Q_THRESHOLD = 8192   # above this, score matrices stream in q-blocks
_BLOCK_Q = 1024


def _seqshard_applicable(rules, hk: int, buf: int) -> bool:
    """Flash-decoding path: KV cache seq-sharded over the model axis.

    Used when kv heads don't divide the model axis (gemma3 kv=1, qwen3 kv=8
    on a 16-way axis): head_dim-fallback sharding makes QK a sharded-dim
    contraction whose partial scores get all-reduced — (B,H,S) fp32 per layer,
    measured at ~7.5 GB/step on qwen3-0.6b decode_32k. Sharding the *sequence*
    instead turns the combine into a (B,H,D)-sized log-sum-exp psum.
    """
    if rules is None or not hasattr(rules, "mesh"):
        return False
    mesh = rules.mesh
    if "model" not in mesh.shape or mesh.shape["model"] <= 1:
        return False
    nm = mesh.shape["model"]
    return hk % nm != 0 and buf % nm == 0


def _decode_attn_seqshard(q, k_new, v_new, cache, pos, mask_kind, window,
                          rules, g: int):
    """One decode step with a sequence-sharded cache (flash-decoding).

    Inside shard_map over the model axis: write the new KV into the owning
    shard, compute local partial attention with a running max, and combine
    across shards with exp-rescaled psums. Per-layer wire: O(B·H·D) floats
    (vs O(B·H·S) for the head_dim-fallback all-reduce).

    q: (B,1,H,D); k_new/v_new: (B,1,Hk,D); cache k/v: (B,buf,Hk,D).
    Returns (out (B,1,H,D), new_cache).
    """
    import math as _math

    mesh = rules.mesh
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape) or None
    if dp is not None:
        dp_size = math.prod(mesh.shape[a] for a in dp)
        if q.shape[0] % dp_size != 0:  # e.g. long_500k batch=1: replicate
            dp = None
    nm = mesh.shape["model"]
    buf = cache["k"].shape[1]
    slot = jnp.asarray(pos % buf if mask_kind == "sliding" else pos,
                       jnp.int32)

    def local(qr, kn, vn, kl, vl, slot_g, pos_g):
        B, S_loc, Hk, D = kl.shape
        mi = jax.lax.axis_index("model")
        lo = mi * S_loc
        rel = slot_g - lo
        in_range = (rel >= 0) & (rel < S_loc)
        relc = jnp.clip(rel, 0, S_loc - 1)
        # NOTE §Perf B2: a row-granular .at[relc].set() variant was tried and
        # REFUTED — bytes-accessed rose 9% (the gather of the original row is
        # extra traffic; XLA already fuses this whole-buffer select).
        kl2 = jax.lax.dynamic_update_slice(kl, kn, (0, relc, 0, 0))
        vl2 = jax.lax.dynamic_update_slice(vl, vn, (0, relc, 0, 0))
        kl = jnp.where(in_range, kl2, kl)
        vl = jnp.where(in_range, vl2, vl)

        kr = jnp.repeat(kl, g, axis=2) if g > 1 else kl
        vr = jnp.repeat(vl, g, axis=2) if g > 1 else vl
        scores = jnp.einsum("bqhd,bkhd->bhqk", qr, kr,
                            preferred_element_type=jnp.float32)
        scores = scores / _math.sqrt(D)
        idx = lo + jnp.arange(S_loc, dtype=jnp.int32)      # global slot ids
        if mask_kind == "sliding":
            total = S_loc * nm
            age = (slot_g - idx) % total
            ok = age < jnp.minimum(pos_g + 1, total)
        else:
            ok = idx <= pos_g
        scores = jnp.where(ok[None, None, None, :], scores, NEG_INF)
        m = jnp.maximum(jnp.max(scores, axis=-1), -1e30)   # (B,H,1)
        p = jnp.exp(scores - m[..., None])
        denom = jnp.sum(p, axis=-1)                        # (B,H,1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr)
        M = jax.lax.pmax(m, "model")
        scale = jnp.exp(m - M)                             # (B,H,1)
        o_g = jax.lax.psum(o * scale.transpose(0, 2, 1)[..., None]
                           .astype(o.dtype), "model")
        d_g = jax.lax.psum(denom * scale, "model")
        out = o_g / jnp.maximum(d_g, 1e-30).transpose(0, 2, 1)[..., None] \
            .astype(o_g.dtype)
        return out.astype(qr.dtype), kl, vl

    smapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, None, None, None),
                  P(dp, None, None, None), P(dp, "model", None, None),
                  P(dp, "model", None, None), P(), P()),
        out_specs=(P(dp, None, None, None), P(dp, "model", None, None),
                   P(dp, "model", None, None)),
        check_vma=False,
    )
    out, kc, vc = smapped(q, k_new, v_new, cache["k"], cache["v"], slot,
                          jnp.asarray(pos, jnp.int32))
    return out, {"k": kc, "v": vc}


def _sdpa_blocked(q, k, v, kv_pos, mask_kind, window, rules, g: int):
    """Query-blocked attention: scores never exceed (B, H, block, K).

    The memory analogue of flash attention — full softmax rows per q block
    (no online renormalization needed since the whole row fits), scanned over
    blocks with `lax.map`.
    """
    B, S, H, D = q.shape
    nb = S // _BLOCK_Q
    qb = q.reshape(B, nb, _BLOCK_Q, H, D).swapaxes(0, 1)  # (nb, B, blk, H, D)
    qpos = jnp.arange(S, dtype=jnp.int32).reshape(nb, _BLOCK_Q)

    def one(args):
        qblk, qp = args
        bias = _mask_bias("train", mask_kind, _BLOCK_Q, k.shape[1],
                          qp, kv_pos, None, window)
        return _sdpa(qblk, k, v, bias, rules, g)

    out = jax.lax.map(one, (qb, qpos))                    # (nb, B, blk, H, D)
    return out.swapaxes(0, 1).reshape(B, S, H, D)


def attention(
    p, cfg, x, *,
    rules=None,
    mask_kind: str = "causal",
    window: Optional[int] = None,
    theta: Optional[float] = None,
    mode: str = "train",          # train | prefill | decode
    pos_offset=0,                 # decode: current position (traced ok)
    cache: Optional[dict] = None,
    cross_x: Optional[jax.Array] = None,   # encoder output for cross-attn
    cache_len: Optional[int] = None,       # static cache buffer length
):
    """Returns (y, new_cache | None)."""
    B, S, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // hk
    theta = cfg.rope_theta if theta is None else theta
    is_cross = cross_x is not None

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kv_src = cross_x if is_cross else x
    if mode == "decode" and is_cross and cache is not None:
        k = cache["k"]
        v = cache["v"]
    else:
        k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])

    if "q_norm" in p:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps) if not is_cross else k

    if not is_cross:
        if mode == "decode":
            q_pos = jnp.full((S,), 0, jnp.int32) + pos_offset
        else:
            q_pos = jnp.arange(S, dtype=jnp.int32)
        q = rope(q, q_pos, theta)
        if mode == "decode" and cache is not None:
            k = rope(k, q_pos, theta)
        elif mode != "decode":
            k = rope(k, jnp.arange(k.shape[1], dtype=jnp.int32), theta)

    # Activations never shard head_dim: it is the QK contraction dim, and a
    # sharded contraction all-reduces partial *scores* — measured 48 GiB/step
    # f32 on granite train_4k (heads=24 ∤ 16 → the old (None,"tp") fallback).
    # When heads don't divide the model axis GSPMD now picks the layout
    # (params keep the head_dim fallback for storage sharding).
    q = _c(rules, q, (("batch",), (None,), ("tp",), (None,)))
    k = _c(rules, k, (("batch",), (None,), ("tp",), (None,)))
    v = _c(rules, v, (("batch",), (None,), ("tp",), (None,)))

    new_cache = None
    if mode in ("train", "prefill"):
        kv_len = k.shape[1]
        kv_pos = jnp.arange(kv_len, dtype=jnp.int32)
        if S >= _BLOCK_Q_THRESHOLD and S % _BLOCK_Q == 0:
            # blocked (flash-style) attention: O(S·block) score memory
            out = _sdpa_blocked(q, k, v, kv_pos, mask_kind, window, rules, g)
        else:
            bias = _mask_bias(mode, mask_kind, S, kv_len,
                              jnp.arange(S, dtype=jnp.int32), kv_pos, None,
                              window)
            out = _sdpa(q, k, v, bias, rules, g)
        if mode == "prefill":
            # cache layout invariant: position p lives at slot p % buf
            buf = kv_len if cache_len is None else cache_len
            if mask_kind == "sliding" and window is not None:
                buf = min(buf, window)
            take = min(kv_len, buf)
            klast, vlast = k[:, kv_len - take:], v[:, kv_len - take:]
            if take == buf and kv_len % buf != 0:
                shift = kv_len % buf
                klast = jnp.roll(klast, shift, axis=1)
                vlast = jnp.roll(vlast, shift, axis=1)
            if take == buf:
                kc, vc = klast, vlast
            else:
                kc = jnp.zeros((B, buf, hk, hd), k.dtype)
                vc = jnp.zeros((B, buf, hk, hd), v.dtype)
                kc = jax.lax.dynamic_update_slice(kc, klast, (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, vlast, (0, 0, 0, 0))
            new_cache = {"k": kc, "v": vc}
    elif mode == "decode":
        if is_cross:
            kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
            bias = jnp.zeros((S, k.shape[1]), jnp.float32)
            out = _sdpa(q, k, v, bias, rules, g)
            new_cache = cache
        elif _seqshard_applicable(rules, hk, cache["k"].shape[1]):
            out, new_cache = _decode_attn_seqshard(
                q, k, v, cache, pos_offset, mask_kind, window, rules, g)
        else:
            kc, vc = cache["k"], cache["v"]
            buf = kc.shape[1]
            slot = (pos_offset % buf) if (mask_kind == "sliding") else pos_offset
            slot = jnp.asarray(slot, jnp.int32)
            kc = jax.lax.dynamic_update_slice(kc, k, (0, slot, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, slot, 0, 0))
            new_cache = {"k": kc, "v": vc}
            # validity/causality of cache slots
            idx = jnp.arange(buf, dtype=jnp.int32)
            if mask_kind == "sliding":
                # slot ages: written within the last `window` positions
                age = (slot - idx) % buf
                ok = (age < jnp.minimum(pos_offset + 1, buf))
            else:
                ok = idx <= pos_offset
            bias = jnp.where(ok, 0.0, NEG_INF)[None, None, :]  # (1, S=1, buf)
            bias = jnp.broadcast_to(bias, (B, S, buf))
            out = _sdpa(q, kc, vc, bias, rules, g)
    else:
        raise ValueError(mode)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = _c(rules, y, (("batch",), ("sp",), (None,)))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def init_mlp(key, d: int, d_ff: int, dtype, gated: bool = True) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": PLeaf(dense_init(ks[0], (d, d_ff), dtype),
                      (("fsdp",), ("tp",))),
        "w_down": PLeaf(dense_init(ks[1], (d_ff, d), dtype),
                        (("tp",), ("fsdp",))),
    }
    if gated:
        p["w_gate"] = PLeaf(dense_init(ks[2], (d, d_ff), dtype),
                            (("fsdp",), ("tp",)))
    return p


def mlp(p, x, act: str, rules=None):
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = _act(act)(gate) * up
    else:
        h = _act(act)(up)
    h = _c(rules, h, (("batch",), ("sp",), ("tp",)))
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return _c(rules, y, (("batch",), ("sp",), (None,)))


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based grouped GEMM, capacity drop policy)
# ---------------------------------------------------------------------------


def init_moe(key, cfg, dtype) -> dict:
    d, dff = cfg.d_model, cfg.d_ff
    e = cfg.expert_pad_to  # padded expert count for EP divisibility
    ks = jax.random.split(key, 4)
    return {
        "router": PLeaf(dense_init(ks[0], (d, e), dtype), (("fsdp",), (None,))),
        "w_gate": PLeaf(dense_init(ks[1], (e, d, dff), dtype),
                        (("expert",), ("fsdp",), (None,))),
        "w_up": PLeaf(dense_init(ks[2], (e, d, dff), dtype),
                      (("expert",), ("fsdp",), (None,))),
        "w_down": PLeaf(dense_init(ks[3], (e, dff, d), dtype),
                        (("expert",), (None,), ("fsdp",))),
    }


def moe(p, cfg, x, act: str, rules=None, capacity_factor: float | None = None):
    """x: (B, S, D) → (B, S, D). Sort-based dispatch, EP over 'expert'."""
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity
    B, S, D = x.shape
    E = cfg.expert_pad_to
    E_real = cfg.num_experts
    K = cfg.experts_per_tok
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt, p["router"],
                        preferred_element_type=jnp.float32)
    if E_real < E:  # padded experts never routed
        pad_bias = jnp.where(jnp.arange(E) < E_real, 0.0, NEG_INF)
        logits = logits + pad_bias[None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)          # (T, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # flatten (token, k) assignments and sort by expert id
    flat_e = top_i.reshape(-1)                      # (T·K,)
    flat_t = jnp.repeat(jnp.arange(T), K)           # (T·K,)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e)                     # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]

    C = max(int(math.ceil(T * K / E * capacity_factor)), 1)
    # rank of each assignment within its expert group
    counts = jnp.bincount(se, length=E)             # (E,)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - starts[se]
    keep = rank < C
    slot = jnp.where(keep, se * C + jnp.clip(rank, 0, C - 1), E * C)  # drop→OOB

    # gather tokens into the (E, C, D) expert buffer
    buf = jnp.zeros((E * C + 1, D), xt.dtype).at[slot].set(xt[st])
    buf = buf[:-1].reshape(E, C, D)
    buf = _c(rules, buf, (("expert",), (None,), (None,)))

    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = _act(act)(gate) * up
    h = _c(rules, h, (("expert",), (None,), (None,)))
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = _c(rules, out, (("expert",), (None,), (None,)))

    # scatter back with routing weights
    out_flat = out.reshape(E * C, D)
    contrib = jnp.where(keep[:, None], out_flat[jnp.clip(slot, 0, E * C - 1)], 0.0)
    y = jnp.zeros((T, D), out.dtype).at[st].add(contrib * sw[:, None].astype(out.dtype))
    return y.reshape(B, S, D)
