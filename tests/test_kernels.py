"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dual_cd_block, flash_attn, odm_grad, ops, ref, rbf_gram


KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


class TestRbfGram:
    @pytest.mark.parametrize("M,N,D", [(64, 64, 32), (128, 64, 64),
                                       (64, 128, 96)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, M, N, D, dtype):
        x = jax.random.normal(KEY, (M, D), dtype)
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (N, D), dtype)
        got = rbf_gram.rbf_gram(x, z, gamma=0.2, bm=32, bn=32, bd=32,
                                interpret=True)
        want = ref.rbf_gram(x.astype(jnp.float32), z.astype(jnp.float32),
                            0.2)
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
            < _tol(dtype)

    def test_signed(self):
        M, N, D = 64, 64, 32
        x = jax.random.normal(KEY, (M, D))
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (N, D))
        yx = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 2), (M,)))
        yz = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 3), (N,)))
        got = rbf_gram.rbf_gram(x, z, yx, yz, gamma=0.5, signed=True,
                                bm=32, bn=32, bd=32, interpret=True)
        want = ref.signed_rbf_gram(x, z, yx, yz, 0.5)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5

    @pytest.mark.parametrize("M,N,D", [(100, 70, 33), (33, 190, 17)])
    def test_ops_wrapper_ragged(self, M, N, D):
        x = jax.random.normal(KEY, (M, D))
        z = jax.random.normal(jax.random.fold_in(KEY, 1), (N, D))
        got = ops.rbf_gram(x, z, 0.3, bm=32, bn=32, bd=32)
        want = ref.rbf_gram(x, z, 0.3)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4


class TestDualCdBlock:
    def test_tile_sweep_matches_ref(self):
        from repro.core import kernel_fns as kf
        M, B = 128, 32
        x = jax.random.normal(KEY, (M, 8))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 1), (M,)))
        Q = kf.signed_gram(kf.KernelSpec("rbf", 0.5), x, y)
        qb = dual_cd_block.extract_diag_blocks(Q, B)
        a0 = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 2),
                                       (M // B, 2 * B))) * 0.01
        u0 = jax.random.normal(jax.random.fold_in(KEY, 3), (M // B, B)) * 0.1
        kw = dict(c=2.0, ups=0.5, theta=0.1, mscale=float(M), n_steps=24)
        a1, u1, _ = dual_cd_block.cd_block_sweep(qb, a0, u0, interpret=True,
                                              **kw)
        a2, u2 = ref.cd_block_sweep(qb, a0, u0, **kw)
        assert float(jnp.max(jnp.abs(a1 - a2))) < 1e-6
        assert float(jnp.max(jnp.abs(u1 - u2))) < 1e-5

    # name: (K, nblk, exit_tol, an all-padding tile or None, gram source)
    GROUPED = {
        "exits-at-different-steps": (1, 5, 0.05, None, "dense"),
        "fixed-steps-vs-ref": (2, 7, 0.0, None, "mfree"),
        "all-padding-tile": (2, 5, 0.05, 2, "dense"),
        "ragged-one-partition": (1, 7, 0.05, 6, "mfree"),
    }

    @pytest.mark.parametrize("case", list(GROUPED))
    def test_grouped_sweep_equals_each_tile_alone(self, case):
        """Tiles swept in lockstep groups (the two-launch sweep and the
        fused pass) end exactly where a sweep of each tile alone ends —
        duals, u and the applied steps, bit for bit — whatever the tile
        count, where tiles exit, or padding; the fused pass's u_d is the
        Gram product of that step."""
        from repro.core import kernel_fns as kf
        from repro.kernels import gram
        K, nblk, exit_tol, pad, source = self.GROUPED[case]
        B, G = 16, dual_cd_block.group_size(jnp.float32)
        M = nblk * B
        spec = kf.KernelSpec("rbf", 0.5)
        x = jax.random.normal(KEY, (K, M, 6))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 1), (K, M)))
        Q = jax.vmap(lambda a, b: kf.signed_gram(spec, a, b))(x, y)
        qb = jax.vmap(lambda q: dual_cd_block.extract_diag_blocks(q, B))(Q)
        # tiles start from violations decades apart, so they exit unevenly
        scale = 10.0 ** -(jnp.arange(nblk) % 3)[None, :, None]
        a0 = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 2),
                                       (K, nblk, 2 * B))) * 0.01
        u0 = jax.random.normal(jax.random.fold_in(KEY, 3),
                               (K, nblk, B)) * scale
        v = jnp.ones((K, nblk, B))
        if pad is not None:
            v = v.at[:, pad].set(0.0)
        kw = dict(c=2.0, ups=0.5, theta=0.1, mscale=float(M), n_steps=2 * B,
                  exit_tol=exit_tol)
        alone = [[dual_cd_block.cd_block_sweep(
            qb[k, t:t + 1], a0[k, t:t + 1], u0[k, t:t + 1],
            valids=v[k, t:t + 1], interpret=True, **kw)
            for t in range(nblk)] for k in range(K)]
        want_a, want_u, want_s = (
            jnp.stack([jnp.concatenate([r[n] for r in row]) for row in alone])
            for n in range(3))
        a, u, s = dual_cd_block.cd_block_sweep(qb, a0, u0, valids=v,
                                               interpret=True, **kw)
        assert bool(jnp.all(a == want_a)) and bool(jnp.all(u == want_u))
        assert bool(jnp.all(s == want_s))
        if exit_tol > 0:
            assert len(set(s[0, :G].tolist())) > 1, s      # uneven exits
        if pad is not None:
            assert bool(jnp.all(a[:, pad] == a0[:, pad]))
        if exit_tol == 0:
            r_a, r_u = ref.cd_block_sweep(
                qb.reshape(-1, B, B), a0.reshape(-1, 2 * B),
                u0.reshape(-1, B), **{k: kw[k] for k in kw if k != "exit_tol"})
            assert bool(jnp.all(s == 2 * B))
            assert float(jnp.max(jnp.abs(a.reshape(-1, 2 * B) - r_a))) < 1e-6
            assert float(jnp.max(jnp.abs(u.reshape(-1, B) - r_u))) < 1e-5

        src = (gram.DenseSource(Q) if source == "dense" else
               gram.make_kernel_source(spec, x, y, bm=B, bn=B,
                                       interpret=True))
        fa, ud, fs = dual_cd_block.fused_cd_pass(qb, src, a0, u0, v,
                                                 interpret=True, **kw)
        assert bool(jnp.all(fa == want_a)) and bool(jnp.all(fs == want_s))
        d = (fa - a0).reshape(K, nblk, 2, B)
        want_ud = src.matvec((d[:, :, 0] - d[:, :, 1]).reshape(K, M))
        assert float(jnp.max(jnp.abs(ud - want_ud))) < 1e-5

        counts = dual_cd_block.step_counts(s, G)
        assert counts[:, 0].tolist() == jnp.sum(want_s, axis=1).tolist()
        longest = [max(row[i:i + G]) for row in want_s.tolist()
                   for i in range(0, nblk, G)]
        assert int(jnp.sum(counts[:, 1])) == G * sum(longest)

    def test_full_solve_reaches_exact_objective(self):
        from repro.core import dual_cd as cd, kernel_fns as kf, odm
        M = 96
        x = jax.random.normal(KEY, (M, 6))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 1), (M,)))
        Q = kf.signed_gram(kf.KernelSpec("rbf", 0.5), x, y)
        p = odm.ODMParams()
        alpha, kkt, _ = ops.dual_cd_solve(Q, c=p.c, ups=p.ups, theta=p.theta,
                                          mscale=float(M), block=32,
                                          tol=1e-6)
        exact = cd.solve(Q, p, mscale=float(M), tol=1e-6, max_sweeps=500)
        o1 = odm.dual_objective(Q, alpha, p, float(M))
        o2 = odm.dual_objective(Q, exact.alpha, p, float(M))
        assert abs(float(o1 - o2)) < 1e-4
        assert float(kkt) < 1e-5


class TestOdmGrad:
    @pytest.mark.parametrize("M,d", [(128, 64), (256, 32), (96, 130)])
    def test_matches_ref(self, M, d):
        x = jax.random.normal(KEY, (M, d))
        y = jnp.sign(jax.random.normal(jax.random.fold_in(KEY, 1), (M,)))
        w = jax.random.normal(jax.random.fold_in(KEY, 2), (d,)) * 0.2
        got = ops.odm_grad(w, x, y, lam=1.0, theta=0.1, ups=0.5, bm=32)
        want = ref.odm_grad(w, x, y, lam=1.0, theta=0.1, ups=0.5)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


class TestFlashAttn:
    @pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                               (False, None)])
    def test_matches_ref(self, causal, window):
        B, Hq, Hkv, T, D = 2, 4, 2, 128, 64
        q = jax.random.normal(KEY, (B, Hq, T, D)) * 0.3
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, T, D)) * 0.3
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, T, D)) * 0.3
        got = flash_attn.flash_attention(q, k, v, causal=causal,
                                         window=window, bq=32, bk=32,
                                         interpret=True)
        want = ref.mha(q, k, v, causal=causal, window=window)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-6

    def test_decode_history(self):
        """T < S: queries at the end of a longer kv history."""
        B, Hq, Hkv, T, S, D = 1, 4, 2, 32, 128, 64
        q = jax.random.normal(KEY, (B, Hq, T, D)) * 0.3
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, S, D)) * 0.3
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, S, D)) * 0.3
        got = flash_attn.flash_attention(q, k, v, causal=True, bq=32, bk=32,
                                         interpret=True)
        want = ref.mha(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-6

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        B, Hq, Hkv, T, D = 1, 2, 1, 64, 32
        q = (jax.random.normal(KEY, (B, Hq, T, D)) * 0.3).astype(dtype)
        k = (jax.random.normal(jax.random.fold_in(KEY, 1),
                               (B, Hkv, T, D)) * 0.3).astype(dtype)
        v = (jax.random.normal(jax.random.fold_in(KEY, 2),
                               (B, Hkv, T, D)) * 0.3).astype(dtype)
        got = flash_attn.flash_attention(q, k, v, causal=True, bq=32, bk=32,
                                         interpret=True)
        want = ref.mha(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32), causal=True)
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
            < _tol(dtype)


class TestBlockedFlashVJP:
    """The model-side scan flash (attention.py) — grads vs reference."""

    @pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                               (False, None)])
    def test_grads(self, causal, window):
        from repro.models import attention as A
        B, T, H, KV, dh = 2, 50, 4, 2, 32
        q = jax.random.normal(KEY, (B, T, H, dh)) * 0.4
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, KV, dh)) * 0.4
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, KV, dh)) * 0.4

        def f(q, k, v):
            o = A._blocked_flash(q, k, v, causal=causal, window=window,
                                 q_offset=0, bk=16)
            return jnp.sum(jnp.sin(o))

        def g(q, k, v):
            o = ref.mha(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                        jnp.moveaxis(v, 2, 1), causal=causal, window=window)
            return jnp.sum(jnp.sin(jnp.moveaxis(o, 1, 2)))

        g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-5
