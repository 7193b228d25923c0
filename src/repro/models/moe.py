"""Mixture-of-Experts FFN (dbrx top-4, llama4-scout top-1 + shared expert).

Dispatch is scatter-based (GShard semantics without the (T, E, C) one-hot
blow-up): router top-k picks experts; each (token, k) slot's position
inside its expert is a cumsum over the one-hot assignment matrix (T·k × E
ints — cheap); tokens scatter-add into an (E, C, d) buffer, experts run as
one batched einsum (E sharded over the mesh "model" axis = expert
parallelism; the scatter/gather lower to XLA collectives standing in for
the all-to-all), and results gather back weighted by the gate.

Capacity C = ceil(top_k · T / E · capacity_factor); overflow tokens drop
(contribute zero), standard GShard behaviour. An auxiliary load-balance
loss (Switch-style) is returned for the train loop.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ArchConfig
from repro.models import layers as L

Array = jax.Array


def init(key, cfg: ArchConfig, dtype):
    moe = cfg.moe
    d_ff = moe.d_ff_expert or cfg.d_ff
    E = moe.n_experts
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    scale_in = cfg.d_model ** -0.5
    scale_out = d_ff ** -0.5
    p = {
        "router": {"w": jax.random.normal(kr, (cfg.d_model, E), dtype) * scale_in},
        "wi": jax.random.normal(k1, (E, cfg.d_model, d_ff), dtype) * scale_in,
        "wg": jax.random.normal(k2, (E, cfg.d_model, d_ff), dtype) * scale_in,
        "wo": jax.random.normal(k3, (E, d_ff, cfg.d_model), dtype) * scale_out,
    }
    a = {
        "router": {"w": ("embed", None)},
        "wi": ("experts", "embed", "mlp"),
        "wg": ("experts", "embed", "mlp"),
        "wo": ("experts", "mlp", "embed"),
    }
    if moe.shared_expert:
        p["shared"], a["shared"] = L.mlp_init(ks, cfg.d_model, d_ff,
                                              cfg.act, dtype)
    return p, a


def forward(p, x: Array, cfg: ArchConfig, compute_dtype,
            full_capacity: bool = False) -> tuple[Array, Array]:
    """x (B, S, D) -> (out (B, S, D), aux_loss ()).

    Dispatches to the shard_map expert-parallel path when a mesh is active
    (true all-to-alls; see forward_sharded) and the expert count divides
    the model axis; otherwise runs the single-device scatter path below.

    full_capacity=True sets C = T (an expert can never receive more than T
    tokens), guaranteeing zero drops — used by the decode path, where T is
    tiny and train/serve consistency matters more than the buffer size.
    """
    mesh = sharding._ACTIVE["mesh"]
    if mesh is not None and "model" in mesh.shape \
            and cfg.moe.n_experts % mesh.shape["model"] == 0 \
            and x.shape[0] % _token_shards(mesh) == 0:
        return forward_sharded(p, x, cfg, compute_dtype, mesh,
                               full_capacity=full_capacity)
    return _forward_local(p, x, cfg, compute_dtype,
                          full_capacity=full_capacity)


def _token_shards(mesh) -> int:
    n = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    return n


def _forward_local(p, x: Array, cfg: ArchConfig, compute_dtype,
                   full_capacity: bool = False) -> tuple[Array, Array]:
    moe = cfg.moe
    B, S, D = x.shape
    E, k = moe.n_experts, moe.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt @ p["router"]["w"].astype(compute_dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)      # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e (frac_tokens_e * mean_prob_e)
    frac = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32),
                    axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    if full_capacity:
        C = T
    else:
        C = min(int(-(-k * T // E) * moe.capacity_factor), T)
    C = max(C, 1)

    flat_e = expert_ids.reshape(-1)                      # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)          # (T*k, E)
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C                                      # capacity mask

    # scatter tokens into the (E, C, D) buffer
    xk = jnp.repeat(xt, k, axis=0)                       # (T*k, D)
    xk = sharding.constrain(xk, ("batch", None))
    w = gate_vals.reshape(-1)                            # (T*k,)
    slot_c = jnp.where(keep, slot, 0)
    e_c = jnp.where(keep, flat_e, 0)
    contrib = jnp.where(keep[:, None], xk, 0.0)
    contrib = sharding.constrain(contrib, ("batch", None))
    buf = jnp.zeros((E, C, D), compute_dtype)
    buf = buf.at[e_c, slot_c].add(contrib.astype(compute_dtype),
                                  mode="drop")
    buf = sharding.constrain(buf, ("experts", None, "embed"))

    # expert FFN as batched einsums (E on the model axis = EP)
    wi = p["wi"].astype(compute_dtype)
    wg = p["wg"].astype(compute_dtype)
    wo = p["wo"].astype(compute_dtype)
    if cfg.act == "silu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wi)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, wi))
    h = sharding.constrain(h, ("experts", None, "mlp"))
    eout = jnp.einsum("ecf,efd->ecd", h, wo)             # (E, C, D)

    # gather back with gate weighting
    eout = sharding.constrain(eout, ("experts", None, "embed"))
    out_k = eout[e_c, slot_c]                            # (T*k, D)
    out_k = sharding.constrain(out_k, ("batch", None))
    out_k = jnp.where(keep[:, None], out_k, 0.0) * w[:, None].astype(compute_dtype)
    out = jnp.sum(out_k.reshape(T, k, D), axis=1)
    out = sharding.constrain(out, ("batch", None))

    if moe.shared_expert:
        out = out + L.apply_mlp(p["shared"], xt, cfg.act, compute_dtype)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# expert-parallel path (shard_map + all-to-all)
# ---------------------------------------------------------------------------
# Under pjit auto-sharding, scatter/gather across a sharded expert dim
# lowers to full-buffer all-reduces (measured: ~6.8 TB/device/step on
# dbrx-132b train_4k). Expert parallelism needs *all-to-alls*: each data
# shard routes its own tokens locally, sends per-expert slices to the
# model-axis peer that owns the expert, and receives its expert's tokens
# from every peer. shard_map expresses this directly with
# lax.all_to_all; traffic drops to k·T·d bytes per layer total — the
# theoretical minimum for token routing (measured: ~256x less wire bytes).
#
# Mesh contract: tokens sharded over ("pod","data"); experts over "model"
# (weights wi/wg/wo sharded on their leading E dim). Every (pod, data) row
# has the full expert set in its model group, so the a2a stays within the
# row — no cross-row traffic.

def forward_sharded(p, x: Array, cfg: ArchConfig, compute_dtype, mesh,
                    full_capacity: bool = False) -> tuple[Array, Array]:
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    B, S, D = x.shape
    E, k = moe.n_experts, moe.top_k
    n_tok_shards = _token_shards(mesh)
    n_exp = mesh.shape["model"]
    E_loc = E // n_exp
    T_row = (B // n_tok_shards) * S        # tokens per (pod,data) row
    if S % n_exp != 0:
        return _forward_local(p, x, cfg, compute_dtype,
                              full_capacity=full_capacity)
    T_m = (B // n_tok_shards) * (S // n_exp)   # tokens per rank
    if full_capacity:
        C_m = T_m
    else:
        C_m = max(1, min(int(-(-k * T_m // E) * moe.capacity_factor), T_m))

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    # tokens sharded over BOTH the batch (data/pod) and sequence (model)
    # dims: every rank routes a disjoint token slice — no slicing inside
    # the block, so the backward stays collective-free on the input path.
    x_spec = P(batch_axes, "model", None)
    w_repl = P()
    w_exp = P("model")                     # leading E dim of expert weights

    def block(xm, router_w, wi, wg, wo):
        # xm: (B_row, S/n_exp, D) — this rank's disjoint token slice
        Bl, Sl, _ = xm.shape
        xm = xm.reshape(Bl * Sl, D)

        logits = (xm @ router_w.astype(compute_dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        frac = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E,
                                       dtype=jnp.float32), axis=0)
        mean_p = jnp.mean(probs, axis=0)
        axes_all = batch_axes + ("model",)
        aux = E * jnp.sum(jax.lax.pmean(frac, axes_all) *
                          jax.lax.pmean(mean_p, axes_all))

        # local dispatch of this rank's slice into (E, C_m, D)
        flat_e = expert_ids.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        slot = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                   flat_e[:, None], axis=1)[:, 0]
        keep = slot < C_m
        slot_c = jnp.where(keep, slot, 0)
        e_c = jnp.where(keep, flat_e, 0)
        xk = jnp.repeat(xm, k, axis=0)
        contrib = jnp.where(keep[:, None], xk, 0.0).astype(compute_dtype)
        send = jnp.zeros((E, C_m, D), compute_dtype)
        send = send.at[e_c, slot_c].add(contrib, mode="drop")

        # a2a: split E across model ranks; recv (n_src, E_loc, C_m, D)
        recv = jax.lax.all_to_all(
            send.reshape(n_exp, E_loc, C_m, D), "model",
            split_axis=0, concat_axis=0, tiled=False)

        def ffn(xe, wi_e, wg_e, wo_e):
            # xe (n_src, C_m, D) — one local expert, all source slices
            if cfg.act == "silu":
                h = jax.nn.silu(jnp.einsum("scd,df->scf", xe, wg_e)) * \
                    jnp.einsum("scd,df->scf", xe, wi_e)
            else:
                h = jax.nn.gelu(jnp.einsum("scd,df->scf", xe, wi_e))
            return jnp.einsum("scf,fd->scd", h, wo_e)

        eout = jax.vmap(ffn, in_axes=(1, 0, 0, 0), out_axes=1)(
            recv, wi.astype(compute_dtype), wg.astype(compute_dtype),
            wo.astype(compute_dtype))      # (n_src, E_loc, C_m, D)

        # reverse a2a: results return to their source rank
        back = jax.lax.all_to_all(eout, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(E, C_m, D)

        out_k = back[e_c, slot_c]
        out_k = jnp.where(keep[:, None], out_k, 0.0) * \
            gate_vals.reshape(-1)[:, None].astype(compute_dtype)
        out_m = jnp.sum(out_k.reshape(T_m, k, D), axis=1)
        return out_m.reshape(Bl, Sl, D), aux

    shmapped = jax.shard_map(
        block, mesh=mesh,
        in_specs=(x_spec, w_repl, w_exp, w_exp, w_exp),
        out_specs=(x_spec, P()),
        check_vma=False)
    out, aux = shmapped(x, p["router"]["w"], p["wi"], p["wg"], p["wo"])
    if moe.shared_expert:
        xt = x.reshape(B * S, D)
        out = out + L.apply_mlp(p["shared"], xt, cfg.act,
                                compute_dtype).reshape(B, S, D)
    return out, aux
