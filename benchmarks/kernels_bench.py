"""Pallas kernel micro-benchmarks: interpret-mode correctness timing plus
the XLA-path equivalents they replace (the wall-clock numbers that matter
are TPU-only; on CPU we report the ref-path timings and the kernels'
arithmetic intensities for the roofline discussion).

Also reports the fused-pass op-count comparison: the PR 1 pallas layout
paid one ``pallas_call`` (tile sweeps) + one separate matvec per pass;
the fused pass kernel issues exactly ONE ``pallas_call`` per pass with
the Gram matvec accumulated in-kernel (matvecs per pass reduced by 1).

``run(out, quick=True)`` shrinks every size so the CI smoke tier can
execute the full script path in seconds (tests/test_benchmarks_smoke.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.core import kernel_fns as kf
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)


def run(out, quick: bool = False):
    out.append("# kernels: name,config,seconds,derived")
    # rbf gram XLA path (the kernel's oracle) at a few sizes
    gram_sizes = ((256, 32),) if quick else ((1024, 128), (2048, 256),
                                             (4096, 256))
    for M, D in gram_sizes:
        x = jax.random.normal(KEY, (M, D))
        f = jax.jit(lambda a: ref.rbf_gram(a, a, 0.5))
        t, _ = timed(f, x, warmup=1, iters=3)
        flops = 2 * M * M * D
        out.append(f"kernels,rbf_gram_xla,M={M}_D={D},{t:.4f},"
                   f"gflops={flops / t / 1e9:.1f}")

    # matrix-free gram matvec, every KernelSpec family (the SODM u-refresh
    # path above gram_threshold) vs the dense einsum it replaces
    from repro.kernels import ops
    Km, m, d = (2, 64, 8) if quick else (4, 512, 32)
    xb = jax.random.normal(KEY, (Km, m, d))
    yb = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 9), (Km, m)))
    g = jax.random.normal(jax.random.fold_in(KEY, 10), (Km, m))
    for name in kf.KERNELS:
        spec = kf.make_spec(name, gamma=0.5, degree=2, coef0=1.0)
        t, _ = timed(lambda xb=xb, g=g, spec=spec: ops.gram_matvec(
            xb, g, spec, y=yb, bm=min(64, m), bn=min(64, m)),
            warmup=1, iters=2)
        Qs = jax.vmap(lambda xk, yk: kf.signed_gram(spec, xk, yk))(xb, yb)
        td, _ = timed(lambda Qs=Qs: jnp.einsum("kij,kj->ki", Qs, g),
                      warmup=1, iters=2)
        out.append(f"kernels,gram_matvec_{name},K={Km}_m={m},{t:.4f},"
                   f"family={spec.family()}_dense_einsum={td:.4f}")

    # flash attention XLA-scan path
    from repro.models import attention as A
    for T in ((256,) if quick else (512, 1024)):
        q = jax.random.normal(KEY, (1, T, 8, 64)) * 0.3
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, T, 4, 64)) * 0.3
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, T, 4, 64)) * 0.3
        f = jax.jit(lambda q, k, v: A._blocked_flash(
            q, k, v, causal=True, window=None, q_offset=0,
            bk=256))  # lint: ignore[T001] — micro-bench sweeps this knob
        t, _ = timed(f, q, k, v, warmup=1, iters=3)
        flops = 4 * T * T * 8 * 64  # qk + pv
        out.append(f"kernels,flash_xla,T={T},{t:.4f},"
                   f"gflops={flops / t / 1e9:.1f}")

    # dual CD: paper-style scalar sweeps vs block-Gauss-Southwell
    from repro.core import dual_cd, odm
    M = 256 if quick else 1024
    x = jax.random.normal(KEY, (M, 16))
    y = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 3), (M,)))
    Q = kf.signed_gram(kf.KernelSpec("rbf", 0.5), x, y)
    p = odm.ODMParams()
    f1 = jax.jit(lambda Q: dual_cd.solve(Q, p, mscale=float(M), tol=1e-5,
                                         max_sweeps=100).alpha)
    t1, _ = timed(f1, Q, warmup=1, iters=2)
    out.append(f"kernels,dual_cd_scalar,M={M},{t1:.4f},")
    f2 = jax.jit(lambda Q: dual_cd.solve_block(Q, p, mscale=float(M),
                                               block=256,  # lint: ignore[T001] — micro-bench sweeps this knob
                                               tol=1e-5).alpha)
    t2, _ = timed(f2, Q, warmup=1, iters=2)
    out.append(f"kernels,dual_cd_block,M={M},{t2:.4f},"
               f"speedup_vs_scalar={t1 / t2:.2f}")

    # fused pass vs PR 1 layout: pallas_calls + matvec launches per pass.
    # The legacy pass = one cd_block_sweep pallas_call + one separate
    # gram_matvec pallas_call; the fused pass folds the matvec into the
    # sweep kernel — counted by tracing one pass of each.
    from repro.kernels import dual_cd_block as cdk, gram as gram_mod
    Kf, mf, Bf, df = 2, 64, 32, 8
    xf = jax.random.normal(jax.random.fold_in(KEY, 6), (Kf, mf, df))
    yf = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 7), (Kf, mf)))
    spec = kf.KernelSpec("rbf", 0.5)
    qbf = jax.vmap(lambda q: cdk.extract_diag_blocks(q, Bf))(
        jax.vmap(lambda xk, yk: kf.signed_gram(spec, xk, yk))(xf, yf))
    af = jnp.zeros((Kf, mf // Bf, 2 * Bf))
    uf = jnp.zeros((Kf, mf // Bf, Bf))
    vf = jnp.ones((Kf, mf // Bf, Bf))
    src = gram_mod.make_kernel_source(spec, xf, yf, bm=Bf, bn=Bf,
                                      interpret=True)
    cdkw = dict(c=p.c, ups=p.ups, theta=p.theta, mscale=float(mf))
    fused = ops.count_pallas_calls(lambda: cdk.fused_cd_pass(
        qbf, src, af, uf, vf, n_steps=2 * Bf, exit_tol=0.0,
        interpret=True, **cdkw))

    def legacy_pass():
        a2, _, _ = cdk.cd_block_sweep(
            qbf.reshape(-1, Bf, Bf), af.reshape(-1, 2 * Bf),
            uf.reshape(-1, Bf), n_steps=2 * Bf, interpret=True, **cdkw)
        u_d = src.matvec(jnp.zeros((Kf, mf)))
        return a2, u_d

    # count_pallas_calls now walks the jaxpr (sub-jaxprs of jitted
    # constituents included), so no trace-cache clearing is needed
    legacy = ops.count_pallas_calls(legacy_pass)
    out.append(f"kernels,fused_pass_op_count,K={Kf}_m={mf},"
               f"{fused:d},pallas_calls_per_pass_fused={fused}_legacy="
               f"{legacy}_matvec_launches_saved={legacy - fused}")
    # the one-launch pin itself lives in the invariant registry now
    from repro.analysis import invariants as _inv
    _inv.verify("kernels.fused_cd.single_launch")
    assert fused == 1, fused          # and must hold at the bench shapes

    # serving: tiled decision-function scorer (kernels/score.py) — one
    # pallas_call per request batch and O(B·S_block) memory, vs the dense
    # (T, S) Gram the seed predict path materialized per call. Both pins
    # guard the table benchmarks' predict route (sodm.predict /
    # cascade_predict now score through this kernel).
    from repro.kernels import score as score_mod
    Ts, Ss, ds_ = (64, 96, 16) if quick else (512, 1024, 32)
    bt_ = bs_ = 32
    xq = jax.random.normal(jax.random.fold_in(KEY, 11), (Ts, ds_))
    zs = jax.random.normal(jax.random.fold_in(KEY, 12), (Ss, ds_))
    cs = jax.random.normal(jax.random.fold_in(KEY, 13), (Ss,))
    n_calls = ops.count_pallas_calls(lambda: score_mod.score_tiles(
        xq, zs, cs, kind="rbf", gamma=0.5, bt=bt_, bs=bs_, bd=ds_,
        interpret=True))
    dense_bytes = Ts * Ss * 4                 # the (T, S) Gram block
    tile_bytes = (bt_ * bs_ + bt_) * 4        # acc + score scratch in VMEM
    out.append(f"kernels,serve_score_op_count,T={Ts}_S={Ss},{n_calls:d},"
               f"pallas_calls_per_batch={n_calls}_dense_gram_bytes="
               f"{dense_bytes}_tile_scratch_bytes={tile_bytes}")
    _inv.verify("kernels.score.single_launch")
    assert n_calls == 1, n_calls      # and must hold at the bench shapes
    assert tile_bytes < dense_bytes
    t_blk, _ = timed(lambda: score_mod.score_blocked(
        xq, zs, cs, kind="rbf", gamma=0.5, bt=bt_), warmup=1, iters=3)
    t_dense, _ = timed(lambda: score_mod.score_ref(
        xq, zs, cs, kind="rbf", gamma=0.5), warmup=1, iters=3)
    out.append(f"kernels,serve_score_blocked,T={Ts}_S={Ss},{t_blk:.4f},"
               f"dense_ref={t_dense:.4f}")

    # SODM per-level solve: one whole level (K partitions of m rows)
    # through each engine — the hot path the solver-engine layer routes
    from repro.core import engines
    spec = kf.KernelSpec("rbf", 0.5)
    K_parts, m = (2, 64) if quick else (8, 256)
    xs = jax.random.normal(jax.random.fold_in(KEY, 4), (K_parts, m, 16))
    ys = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 5),
                                    (K_parts, m)))
    a0 = jnp.zeros((K_parts, 2 * m))
    t_ref = None
    for name in engines.LEVEL_ENGINES:   # dsvrg is whole-problem, not level
        solver = jax.jit(engines.make_local_solver(name, block=128),  # lint: ignore[T001] — micro-bench sweeps this knob
                         static_argnames=("spec", "params", "tol",
                                         "max_sweeps"))
        t, _ = timed(solver, xs, ys, a0, spec=spec, params=p, tol=1e-5,
                     max_sweeps=100, warmup=1, iters=2)
        t_ref = t if t_ref is None else t_ref
        out.append(f"kernels,sodm_level_{name},K={K_parts}_m={m},{t:.4f},"
                   f"speedup_vs_scalar={t_ref / t:.2f}")
