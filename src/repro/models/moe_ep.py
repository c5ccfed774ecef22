"""Expert-parallel MoE via shard_map + all-to-all (the §Perf hillclimb fix).

The baseline `layers.moe` expresses dispatch as a *global* argsort + scatter.
Under GSPMD that forces token-buffer replication across the expert (model)
axis — the dry-run measured ~20 TB/device/step of collective traffic on
qwen3-moe × train_4k (EXPERIMENTS.md §Perf, hypothesis A1).

This module is the production formulation:

  * activations enter shard_map sharded over the data axes; each *model* rank
    routes an exclusive 1/|model| slice of the local tokens (token-parallel
    routing — routing FLOPs drop |model|-fold too);
  * assignments are binned per destination rank (experts are contiguous per
    rank) with a per-expert, per-source capacity ``cap = ⌈Ts·K/E·cf⌉``;
  * one ragged-free `all_to_all` moves (n_model, e_loc·cap, D) send buffers;
  * local grouped GEMM over the rank's ``e_loc`` experts;
  * the reverse `all_to_all` + local unscatter/combine restores token order;
  * one `all_gather` over the model axis rebuilds the replicated activation.

Wire bytes per layer per device ≈ 2·(n_model·e_loc·cap·D) + T_loc·D
(a2a out/in + gather) — about 0.4 GB for qwen3-moe train_4k vs ~423 GB
measured for the baseline. Exactness: with a non-dropping capacity factor the
outputs match `layers.moe` bit-for-bit up to routing ties (tested).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import NEG_INF, _act


def ep_applicable(cfg, rules, B: int, S: int) -> bool:
    if rules is None or not hasattr(rules, "mesh"):
        return False
    mesh = rules.mesh
    if "model" not in mesh.shape:
        return False
    n_model = mesh.shape["model"]
    dp = math.prod(mesh.shape[a] for a in ("pod", "data") if a in mesh.shape)
    return (cfg.expert_pad_to % n_model == 0
            and S % n_model == 0           # seq is sharded over the model axis
            and B % dp == 0)


def moe_ep(p, cfg, x, act: str, rules, capacity_factor: float | None = None):
    """x: (B, S, D) global → (B, S, D). EP over 'model', DP over data axes."""
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity
    mesh = rules.mesh
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_model = mesh.shape["model"]
    E = cfg.expert_pad_to
    E_real = cfg.num_experts
    K = cfg.experts_per_tok
    e_loc = E // n_model

    def local_fn(x_loc, router, wg, wu, wd):
        # §Perf A3: x enters SEQ-SHARDED over the model axis — each rank owns
        # its token slice outright. The previous replicated-x design made the
        # backward of the block a full (T,D) fp32 all-reduce (the transpose of
        # replication); seq-sharding turns that into the transpose of a
        # slice/gather pair, measured 2–3× cheaper on granite train_4k.
        B_loc, S_loc, D = x_loc.shape
        Ts = B_loc * S_loc
        cap = max(int(math.ceil(Ts * K / E * capacity_factor)), 1)
        xs = x_loc.reshape(Ts, D)

        # -- route my token slice --
        logits = jnp.einsum("td,de->te", xs, router,
                            preferred_element_type=jnp.float32)
        if E_real < E:
            logits += jnp.where(jnp.arange(E) < E_real, 0.0, NEG_INF)[None]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, K)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

        # -- bin (token, k) assignments into the per-expert send queues --
        flat_e = top_i.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(Ts), K)
        flat_w = top_w.reshape(-1)
        order = jnp.argsort(flat_e)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        counts = jnp.bincount(se, length=E)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(Ts * K) - starts[se]
        keep = rank < cap
        slot = jnp.where(keep, se * cap + jnp.clip(rank, 0, cap - 1), E * cap)

        send = jnp.zeros((E * cap + 1, D), xs.dtype).at[slot].set(
            xs[st] * keep[:, None].astype(xs.dtype))[:-1]
        # experts are contiguous per destination rank → rank-major layout
        send = send.reshape(n_model, e_loc * cap, D)

        # -- dispatch / compute / return --
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=True)            # (n_model·eloc·cap, D)?
        recv = recv.reshape(n_model, e_loc, cap, D)
        buf = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_model * cap, D)
        gate = jnp.einsum("egd,edf->egf", buf, wg)
        up = jnp.einsum("egd,edf->egf", buf, wu)
        out = jnp.einsum("egf,efd->egd", _act(act)(gate) * up, wd)
        out = out.reshape(e_loc, n_model, cap, D).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(
            out.reshape(n_model, e_loc * cap, D), "model",
            split_axis=0, concat_axis=0, tiled=True)
        out_flat = back.reshape(E * cap, D)

        # -- undo the local binning, apply combine weights --
        contrib = jnp.where(
            keep[:, None], out_flat[jnp.clip(slot, 0, E * cap - 1)], 0.0)
        y_slice = jnp.zeros((Ts, D), out_flat.dtype).at[st].add(
            contrib * sw[:, None].astype(out_flat.dtype))

        # output stays seq-sharded; GSPMD re-gathers at the block boundary
        return y_slice.reshape(B_loc, S_loc, D).astype(x_loc.dtype)

    smapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(dp_axes or None, "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(dp_axes or None, "model", None),
        check_vma=False,
    )
    return smapped(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
