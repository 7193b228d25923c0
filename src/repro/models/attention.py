"""GQA attention: train/prefill (blocked flash) and cached decode.

Three attention impls, all numerically interchangeable:

* ``flash_xla``   — pure-jnp blocked online-softmax (lax.scan over kv
  blocks). Memory O(T·bk) instead of O(T·S); lowers on every backend, so
  the multi-pod dry-run and CPU tests use it. This is the default.
* ``flash_pallas`` — repro.kernels.flash_attn (TPU Mosaic fast path).
* ``ref``          — O(T·S) reference (tiny smoke shapes only).

Decode attends a (B, S, kv, dh) static cache (vLLM-style preallocation).
Sliding-window layers keep a ring buffer of size W instead of S — this is
what makes recurrentgemma / llama4-scout long_500k-capable. Global layers
at 500k shard the cache along the sequence ("kv_seq" logical axis);
the softmax reductions then lower to psums on the model axis.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ArchConfig
from repro.models import layers as L

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init(key, cfg: ArchConfig, dtype, cross: bool = False):
    """QKV/O projections (+ optional qk-norm scales). ``cross`` builds a
    cross-attention block (q from decoder, kv from encoder memory)."""
    dh = cfg.dh
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    p = {}
    a = {}
    p["wq"], a["wq"] = _proj(kq, cfg.d_model, cfg.n_heads * dh,
                             ("embed", "heads"), cfg.qkv_bias, dtype)
    p["wk"], a["wk"] = _proj(kk, cfg.d_model, cfg.n_kv_heads * dh,
                             ("embed", "kv_heads"), cfg.qkv_bias, dtype)
    p["wv"], a["wv"] = _proj(kv, cfg.d_model, cfg.n_kv_heads * dh,
                             ("embed", "kv_heads"), cfg.qkv_bias, dtype)
    p["wo"], a["wo"] = _proj(ko, cfg.n_heads * dh, cfg.d_model,
                             ("heads", "embed"), False, dtype)
    if cfg.qk_norm and not cross:
        p["qknorm"], a["qknorm"] = L.qk_norm_init(dh, dtype)
    return p, a


def _proj(key, din, dout, axes, bias, dtype):
    return L.dense_init(key, din, dout, dtype, axes=axes, bias=bias)


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _mask_for(j: int, bk: int, S: int, qpos: Array, causal: bool,
              window: Optional[int]) -> Array:
    """Additive mask penalty (T, bk): 0 where attendable, NEG_INF where not.

    Returned as a small 2-D additive term (not a broadcast pred + where):
    XLA hoists loop-invariant mask tensors out of the kv scan, and a
    (nblk, T, bk) f32 penalty is ~1000x smaller than the broadcast
    (nblk, B, T, KV, G, bk) predicate the `where` formulation produces.
    """
    kpos = (j * bk + jnp.arange(bk))[None, :]            # (1, bk)
    mask = kpos <= (S - 1)                               # hide padding
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)   # (T, bk)


def _flash_fwd_scan(qg, kb, vb, S, bk, qpos, causal, window):
    """Online-softmax forward. Returns (out_unnorm acc, m, l)."""
    B, T, KV, G, dh = qg.shape

    def body(carry, inp):
        acc, m, l = carry
        kblk, vblk, j = inp
        logits = jnp.einsum("btkgd,bskd->btkgs", qg,
                            kblk.astype(jnp.float32))     # (B,T,KV,G,bk)
        pen = _mask_for(j, bk, S, qpos, causal, window)
        logits = logits + pen[None, :, None, None, :]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("btkgs,bskd->btkgd", p, vblk.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, T, KV, G, dh), jnp.float32)
    m0 = jnp.full((B, T, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, KV, G), jnp.float32)
    nblk = kb.shape[0]
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nblk)))
    return acc, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _blocked_flash_core(q, k, v, causal, window, q_offset, bk):
    """Flash attention with a flash-style backward.

    The custom VJP is what keeps training memory O(T·bk): differentiating
    through the forward scan would store per-block (B,T,KV,G,bk) logits;
    instead the backward re-walks the kv blocks using only the saved
    softmax stats (m, l) and output — the standard FlashAttention-2
    recomputation, expressed in lax.scan.
    """
    out, _ = _blocked_flash_fwd(q, k, v, causal, window, q_offset, bk)
    return out


def _blocked_flash_fwd(q, k, v, causal, window, q_offset, bk):
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    qg = q.reshape(B, T, KV, G, dh).astype(jnp.float32) * scale
    kb, vb, nblk = _pad_blocks(k, v, bk)
    qpos = (jnp.arange(T) + q_offset)[:, None]
    acc, m, l = _flash_fwd_scan(qg, kb, vb, S, bk, qpos, causal, window)
    lsafe = jnp.maximum(l, 1e-30)
    out = (acc / lsafe[..., None]).reshape(B, T, H, dh).astype(q.dtype)
    return out, (q, k, v, out, m, lsafe)


def _pad_blocks(k, v, bk):
    B, S, KV, dh = k.shape
    nblk = -(-S // bk)
    Sp = nblk * bk
    if Sp != S:
        pad = Sp - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = jnp.moveaxis(k.reshape(B, nblk, bk, KV, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, bk, KV, dh), 1, 0)
    return kb, vb, nblk


def _blocked_flash_bwd(causal, window, q_offset, bk, res, dout):
    q, k, v, out, m, l = res
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    qg = q.reshape(B, T, KV, G, dh).astype(jnp.float32) * scale
    og = out.reshape(B, T, KV, G, dh).astype(jnp.float32)
    dog = dout.reshape(B, T, KV, G, dh).astype(jnp.float32)
    D = jnp.sum(dog * og, axis=-1)                        # (B,T,KV,G)
    kb, vb, nblk = _pad_blocks(k, v, bk)
    Sp = nblk * bk
    qpos = (jnp.arange(T) + q_offset)[:, None]

    def body(dq, inp):
        kblk, vblk, j = inp
        logits = jnp.einsum("btkgd,bskd->btkgs", qg,
                            kblk.astype(jnp.float32))
        pen = _mask_for(j, bk, S, qpos, causal, window)
        logits = logits + pen[None, :, None, None, :]
        p = jnp.exp(logits - m[..., None]) / l[..., None]  # (B,T,KV,G,bk)
        dp = jnp.einsum("btkgd,bskd->btkgs", dog, vblk.astype(jnp.float32))
        dv = jnp.einsum("btkgs,btkgd->bskd", p, dog)
        ds = p * (dp - D[..., None])                       # (B,T,KV,G,bk)
        # qg already carries the softmax scale: dlogits/dq = scale*k,
        # dlogits/dk = qg (scale baked in) — no second scale on dk.
        dq = dq + jnp.einsum("btkgs,bskd->btkgd", ds,
                             kblk.astype(jnp.float32)) * scale
        dk = jnp.einsum("btkgs,btkgd->bskd", ds, qg)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, T, KV, G, dh), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0,
                                  (kb, vb, jnp.arange(nblk)))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sp, KV, dh)[:, :S]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sp, KV, dh)[:, :S]
    dq = dq.reshape(B, T, H, dh).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_blocked_flash_core.defvjp(
    lambda q, k, v, causal, window, q_offset, bk: _blocked_flash_fwd(
        q, k, v, causal, window, q_offset, bk),
    _blocked_flash_bwd)


def _blocked_flash(q: Array, k: Array, v: Array, *, causal: bool,
                   window: Optional[int], q_offset: int,
                   bk: int = 512) -> Array:
    """Online-softmax flash in pure jnp with flash-style custom VJP.

    q (B,T,H,dh), k/v (B,S,KV,dh). GQA handled by reshaping q to
    (B, T, KV, G, dh) so einsums broadcast over the group dim without
    materializing repeated k/v.
    """
    S = k.shape[1]
    bk = min(bk, S)
    out = _blocked_flash_core(q, k, v, causal, window, q_offset, bk)
    # note: dk/dv of the padded tail are dropped by slicing inside the
    # core's bwd reshape; padding only exists when S % bk != 0, and those
    # keys receive zero probability so their grads are zero anyway.
    return out


def _ref_attention(q, k, v, *, causal, window, q_offset):
    from repro.kernels import ref
    # ref.mha wants (B, H, T, D)
    o = ref.mha(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=causal, window=window)
    return jnp.moveaxis(o, 1, 2)


def attend(q: Array, k: Array, v: Array, *, causal: bool = True,
           window: Optional[int] = None, q_offset: int = 0,
           impl: str = "flash_xla") -> Array:
    """q (B, T, H, dh); k/v (B, S, KV, dh) -> (B, T, H, dh).

    When a mesh is active and the head count divides the model axis, the
    flash computation runs under shard_map with q/out sharded over heads
    and k/v replicated on the model axis (gathered once per layer). This
    pins one consistent layout on the 5-D GQA intermediates — letting the
    SPMD partitioner pick leads to conflicting (KV, G) factorizations and
    "involuntary full rematerialization" (measured: TB-scale all-gathers
    inside the bwd scan on dbrx-132b).
    """
    if impl == "flash_pallas":
        from repro.kernels import ops
        o = ops.flash_attention(
            jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
            jnp.moveaxis(v, 2, 1), causal=causal, window=window)
        return jnp.moveaxis(o, 1, 2)
    if impl == "ref":
        return _ref_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    mesh = sharding._ACTIVE["mesh"]
    H = q.shape[2]
    if (mesh is not None and "model" in mesh.shape
            and H % mesh.shape["model"] == 0 and impl == "flash_xla"):
        return _flash_sharded(q, k, v, mesh, causal=causal, window=window,
                              q_offset=q_offset)
    if mesh is not None:
        # heads do not divide the model axis (e.g. 40 heads / 16-way axis,
        # smollm's 9 heads): pin batch-only sharding on the flash operands
        # so the partitioner cannot invent conflicting (KV, G)
        # factorizations (attention compute is then model-axis redundant —
        # the divisibility fallback's price, revisited in §Perf).
        pin = lambda x: sharding.constrain(x, ("batch", None, None, None))
        q, k, v = pin(q), pin(k), pin(v)
        out = _blocked_flash(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
        return pin(out)
    return _blocked_flash(q, k, v, causal=causal, window=window,
                          q_offset=q_offset)


def _flash_sharded(q: Array, k: Array, v: Array, mesh, *, causal: bool,
                   window: Optional[int], q_offset: int) -> Array:
    """Head-parallel flash under shard_map.

    q/out: heads sharded over the model axis; k/v replicated over it (the
    one per-layer kv gather is the price of GQA head parallelism — tiny:
    KV heads only). Each rank expands its local q heads' kv on the fly, so
    the inner flash runs MHA-style (G=1) with no factored-dim ambiguity.
    """
    from jax.sharding import PartitionSpec as P

    B, T, H, dh = q.shape
    KV = k.shape[2]
    n_m = mesh.shape["model"]
    H_loc = H // n_m
    G = H // KV
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    qspec = P(batch_axes, None, "model", None)
    kvspec = P(batch_axes, None, None, None)

    def block(q_loc, k_rep, v_rep):
        m = jax.lax.axis_index("model")
        hidx = m * H_loc + jnp.arange(H_loc)
        kvidx = hidx // G
        k_loc = jnp.take(k_rep, kvidx, axis=2)
        v_loc = jnp.take(v_rep, kvidx, axis=2)
        return _blocked_flash(q_loc, k_loc, v_loc, causal=causal,
                              window=window, q_offset=q_offset)

    return jax.shard_map(block, mesh=mesh,
                         in_specs=(qspec, kvspec, kvspec),
                         out_specs=qspec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# layer-level forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(p, x: Array, cfg: ArchConfig, *, pos: Array,
            causal: bool = True, window: Optional[int] = None,
            use_rope: bool = True, pos3: Optional[Array] = None,
            memory: Optional[Array] = None, impl: str = "flash_xla",
            compute_dtype=jnp.bfloat16) -> Array:
    """Full-sequence attention sublayer (no residual/norm — caller owns).

    memory: encoder output for cross-attention (kv come from memory).
    """
    B, T, D = x.shape
    dh = cfg.dh
    kv_src = x if memory is None else memory
    q = L.apply_dense(p["wq"], x, compute_dtype).reshape(B, T, cfg.n_heads, dh)
    k = L.apply_dense(p["wk"], kv_src, compute_dtype).reshape(
        B, kv_src.shape[1], cfg.n_kv_heads, dh)
    v = L.apply_dense(p["wv"], kv_src, compute_dtype).reshape(
        B, kv_src.shape[1], cfg.n_kv_heads, dh)
    if "qknorm" in p:
        q = L.apply_head_rmsnorm(q, p["qknorm"]["q_scale"])
        k = L.apply_head_rmsnorm(k, p["qknorm"]["k_scale"])
    if use_rope and memory is None:
        if cfg.rope_kind == "mrope" and pos3 is not None:
            q = L.apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = L.apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        elif cfg.rope_kind != "none":
            q = L.apply_rope(q, pos, cfg.rope_theta)
            k = L.apply_rope(k, pos, cfg.rope_theta)
    q = sharding.constrain(q, ("batch", "seq", "heads", None))
    k = sharding.constrain(k, ("batch", "seq", "kv_heads", None))
    v = sharding.constrain(v, ("batch", "seq", "kv_heads", None))
    o = attend(q, k, v, causal=causal and memory is None, window=window,
               impl=impl)
    o = o.reshape(B, T, cfg.n_heads * dh)
    return L.apply_dense(p["wo"], o, compute_dtype)


# ---------------------------------------------------------------------------
# decode with static caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               window: Optional[int] = None, dtype=jnp.bfloat16):
    """Static KV cache for one layer. Window layers allocate min(W, S)."""
    S = max_len if window is None else min(window, max_len)
    shape = (batch, S, cfg.n_kv_heads, cfg.dh)
    kv_axes = ("batch", "kv_seq", "kv_heads", None) if window is None else \
              ("batch", None, "kv_heads", None)
    return ({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)},
            {"k": kv_axes, "v": kv_axes})


def cache_shape(cfg: ArchConfig, batch: int, max_len: int,
                window: Optional[int] = None, dtype=jnp.bfloat16):
    S = max_len if window is None else min(window, max_len)
    shape = (batch, S, cfg.n_kv_heads, cfg.dh)
    kv_axes = ("batch", "kv_seq", "kv_heads", None) if window is None else \
              ("batch", None, "kv_heads", None)
    sds = jax.ShapeDtypeStruct(shape, dtype)
    return {"k": sds, "v": sds}, {"k": kv_axes, "v": kv_axes}


def decode_step(p, cache, x: Array, cfg: ArchConfig, *, pos: Array,
                window: Optional[int] = None, use_rope: bool = True,
                pos3: Optional[Array] = None,
                compute_dtype=jnp.bfloat16):
    """One-token decode. x (B, 1, D); pos () int32 current position.

    Returns (out (B, 1, D), new_cache). Ring-buffer write for window
    layers; full-cache masked attend otherwise.
    """
    B, T, D = x.shape
    assert T == 1
    dh = cfg.dh
    q = L.apply_dense(p["wq"], x, compute_dtype).reshape(B, 1, cfg.n_heads, dh)
    k = L.apply_dense(p["wk"], x, compute_dtype).reshape(B, 1, cfg.n_kv_heads, dh)
    v = L.apply_dense(p["wv"], x, compute_dtype).reshape(B, 1, cfg.n_kv_heads, dh)
    if "qknorm" in p:
        q = L.apply_head_rmsnorm(q, p["qknorm"]["q_scale"])
        k = L.apply_head_rmsnorm(k, p["qknorm"]["k_scale"])
    if use_rope:
        pvec = pos[None] if pos.ndim == 0 else pos
        if cfg.rope_kind == "mrope" and pos3 is not None:
            q = L.apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = L.apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        elif cfg.rope_kind != "none":
            q = L.apply_rope(q, jnp.broadcast_to(pvec, (B, 1)), cfg.rope_theta)
            k = L.apply_rope(k, jnp.broadcast_to(pvec, (B, 1)), cfg.rope_theta)
    S = cache["k"].shape[1]
    slot = pos % S if window is not None else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    # masked attend over the whole static cache
    KV = cfg.n_kv_heads
    G = cfg.n_heads // KV
    qg = q.reshape(B, 1, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    logits = jnp.einsum("btkgd,bskd->btkgs", qg, ck.astype(jnp.float32))
    kpos = jnp.arange(S)
    if window is None:
        valid = kpos <= pos
    else:
        # ring buffer: slot i holds absolute position i + floor stuff; valid
        # iff its absolute position in (pos-window, pos]. Absolute position
        # of slot i: the latest write at or before `pos` congruent to i.
        age = (slot - kpos) % S                          # 0 = newest
        valid = (age < jnp.minimum(pos + 1, S))
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    prob = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("btkgs,bskd->btkgd", prob, cv.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.n_heads * dh).astype(compute_dtype)
    out = L.apply_dense(p["wo"], o, compute_dtype)
    return out, {"k": ck, "v": cv}
