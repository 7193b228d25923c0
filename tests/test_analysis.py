"""The static-analysis battery (ISSUE 6).

Four parts: (1) unit tests for the jaxpr walker + rule engine on small
synthetic programs with KNOWN structure; (2) unit tests for the Pallas
VMEM/tiling checker, including the acceptance case — a deliberately
oversized tile config fails with a per-block sizing report, and the
m=10^6 fused-pass u_d plan is rejected at plan time; (3) ONE uniform
parametrized battery over every declared invariant in
``repro.analysis.invariants`` plus the meta-test that every registered
kernel and training route HAS a declaration; (4) the boundary lint —
seeded fixtures fail, the real tree passes, through the same
``scripts/lint.py`` CLI that CI runs — and the layering of the package:
no lower layer imports a higher one.
"""
import ast
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import boundary_lint as bl
from repro.analysis import invariants as inv
from repro.analysis import jaxpr_lint as jl
from repro.analysis import pallas_check as pc
from repro.api import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "lint")


# ---------------------------------------------------------------------------
# jaxpr_lint: walker + rules on programs with known structure
# ---------------------------------------------------------------------------

class TestJaxprWalker:
    def test_sites_cover_nested_scan_and_cond(self):
        def f(x):
            def body(c, _):
                c = jax.lax.cond(c[0] > 0, lambda v: v * 2.0,
                                 lambda v: v - 1.0, c)
                return c, None
            out, _ = jax.lax.scan(body, x, None, length=3)
            return out

        sites = list(jl.iter_sites(jl.trace(lambda: f(jnp.ones(4)))))
        prims = {s.prim for s in sites}
        assert "scan" in prims and "cond" in prims
        cond_sites = [s for s in sites if s.prim == "cond"]
        assert all(s.path == ("scan_body",) for s in cond_sites)
        # primitives inside the cond branches carry the full frame path
        inner = [s for s in sites if s.path[:2] == ("scan_body", "cond")]
        assert inner, "no sites recorded inside the cond branches"

    def test_loop_depth_counts_while_frames(self):
        def f(x):
            return jax.lax.while_loop(lambda c: c[0] < 10.0,
                                      lambda c: c * 2.0, x)

        sites = list(jl.iter_sites(jl.trace(lambda: f(jnp.ones(2)))))
        body = [s for s in sites if s.path == ("while_body",)]
        cond = [s for s in sites if s.path == ("while_cond",)]
        assert body and cond
        assert all(s.loop_depth == 1 for s in body + cond)

    def test_walks_into_pjit_subjaxprs(self):
        inner = jax.jit(lambda a: a @ a)
        n = jl.count_primitive(lambda: inner(jnp.ones((4, 4))), "dot_general")
        assert n == 1

    def test_scan_lengths(self):
        def f(x):
            a, _ = jax.lax.scan(lambda c, _: (c, None), x, None, length=7)
            b, _ = jax.lax.scan(lambda c, _: (c, None), a, None, length=3)
            return b

        assert sorted(jl.scan_lengths(lambda: f(jnp.ones(2)))) == [3, 7]


class TestJaxprRules:
    def test_max_pallas_calls_flags_excess(self):
        from repro.kernels import score
        x = jnp.ones((16, 8))
        c = jnp.ones((16,))

        def two_launches():
            a = score.score_tiles(x, x, c, kind="rbf", gamma=0.5, bt=8,
                                  bs=8, bd=8, interpret=True)
            b = score.score_tiles(x, x, c, kind="linear", gamma=0.5,
                                  bt=8, bs=8, bd=8, interpret=True)
            return a + b

        assert jl.lint(two_launches, [jl.max_pallas_calls(2)]) == []
        bad = jl.lint(two_launches, [jl.max_pallas_calls(1)])
        assert len(bad) == 1 and "2 x pallas_call" in bad[0].message

    def test_gather_free_flags_fancy_indexing(self):
        x = jnp.ones((8, 4))
        idx = jnp.array([1, 3])
        bad = jl.lint(lambda: x[idx], [jl.gather_free()])
        assert bad and bad[0].rule == "gather_free"
        with pytest.raises(jl.InvariantViolation, match="gather"):
            jl.check(lambda: x[idx], [jl.gather_free()])

    def test_collective_in_scan_body_detected(self):
        mesh = jax.sharding.Mesh(jax.devices()[:1], ("d",))
        from jax.sharding import PartitionSpec as P

        def inside(x):
            def body(c, _):
                return c + jax.lax.psum(c, "d"), None
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out

        def hoisted(x):
            g = jax.lax.psum(x, "d")

            def body(c, _):
                return c + g, None
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out

        rule = jl.no_collectives_in_loops()
        for check_vma in (True, False):
            shm_in = jax.shard_map(inside, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=check_vma)
            got = jl.lint(lambda: shm_in(jnp.ones(4)), [rule])
            # one reduction site, whatever name the psum lowers to
            # (psum_invariant when varying axes are checked, psum if not)
            assert len(got) == 1, (check_vma, got)
            site, what = got[0].message.split(" ", 1)
            assert site.rsplit(":", 1)[1].startswith("psum"), got
            assert what.startswith("inside a loop body"), got
            shm_out = jax.shard_map(hoisted, mesh=mesh, in_specs=P(),
                                    out_specs=P(), check_vma=check_vma)
            assert jl.lint(lambda: shm_out(jnp.ones(4)), [rule]) == []

    def test_allowlisted_collective_passes(self):
        mesh = jax.sharding.Mesh(jax.devices()[:1], ("d",))
        from jax.sharding import PartitionSpec as P

        def f(x):
            def body(c, _):
                return c + jax.lax.psum(c, "d"), None
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out

        shm = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())
        rule = jl.no_collectives_in_loops
        assert jl.lint(lambda: shm(jnp.ones(4)), [rule()])
        # allow-list names the LOWERED primitive (psum -> psum_invariant
        # under shard_map's varying-axes check)
        ok = jl.lint(lambda: shm(jnp.ones(4)),
                     [rule(allow=("psum_invariant",))])
        assert ok == []

    def test_host_sync_in_loop_detected(self):
        def f(x):
            def body(c, _):
                jax.debug.callback(lambda v: None, c)
                return c + 1.0, None
            out, _ = jax.lax.scan(body, x, None, length=2)
            return out

        bad = jl.lint(lambda: f(jnp.ones(2)),
                      [jl.no_host_sync_in_loops()])
        assert bad and "loop body" in bad[0].message

    def test_expect_scan(self):
        def f(x):
            out, _ = jax.lax.scan(lambda c, _: (c, None), x, None,
                                  length=5)
            return out

        thunk = lambda: f(jnp.ones(2))
        assert jl.lint(thunk, [jl.expect_scan(5)]) == []
        bad = jl.lint(thunk, [jl.expect_scan(9)])
        assert bad and "length 9" in bad[0].message


# ---------------------------------------------------------------------------
# pallas_check: VMEM budget + tiling
# ---------------------------------------------------------------------------

class TestPallasCheck:
    def test_default_plans_all_fit(self):
        reports = pc.check_kernels()
        assert set(reports) == set(pc.PLAN_BUILDERS)
        for rep in reports.values():
            assert "TOTAL" in rep

    def test_oversized_tile_config_fails_with_sizing_report(self):
        """Acceptance: a deliberately oversized tile config is rejected
        with a per-block VMEM sizing report."""
        plan = pc.gram_plan(M=8192, N=8192, bm=2048, bn=2048)
        with pytest.raises(pc.PallasBudgetError) as ei:
            pc.check_plan(plan)
        msg = str(ei.value)
        assert "exceeds" in msg and "budget" in msg
        # the report names the offending blocks with shape and bytes
        assert "2048x2048" in msg and "MiB" in msg
        assert "out" in msg and "acc" in msg

    def test_fused_ud_ceiling_at_1e6(self):
        """Acceptance: the partition-resident (nblk, B) u_d block is the
        fused layout's ceiling. Since the labels stream per tile it alone
        is resident, so m = 10^6 fits and m = 2*10^6 fails at PLAN time,
        naming the resident block."""
        for m in (400_000, 1_000_000):
            assert "u_d" in pc.check_plan(pc.fused_cd_plan(m=m))
        with pytest.raises(pc.PallasBudgetError) as ei:
            pc.check_plan(pc.fused_cd_plan(m=2_000_000))
        msg = str(ei.value)
        assert "u_d" in msg and "resident" in msg

    @pytest.mark.parametrize("source", ["kernel", "dense"])
    def test_old_fused_cd_layout_breaks_block_rule(self, source):
        """The (1, 1, 2B) per-tile alpha block on a (K, nblk, 2B) array —
        the fused-CD layout the TPU compiler refused — is flagged, while
        the current (2, B) slab layout passes."""
        K, nblk, B = 4, 8, 256
        old = pc.KernelPlan(
            kernel="fused_cd", grid=(K, nblk, nblk),
            blocks=(pc.Block("a", (1, 1, 2 * B), array=(K, nblk, 2 * B)),
                    pc.Block("u_d", (1, nblk * B), kind="resident",
                             array=(K, nblk * B))))
        with pytest.raises(pc.PallasBudgetError, match="block-shape rule"):
            pc.check_plan(old)
        assert len(pc.tiling_problems(old)) == 2
        new = pc.fused_cd_plan(K=K, m=nblk * B, B=B, source=source)
        assert pc.tiling_problems(new) == []

    def test_block_rule_accepts_aligned_or_full_dims(self):
        ok = pc.KernelPlan(kernel="toy", grid=(1,), blocks=(
            pc.Block("aligned", (8, 128), array=(64, 1024)),
            pc.Block("full", (1, 22), array=(1, 22)),
            pc.Block("lead", (1, 1, 2, 96), array=(3, 5, 2, 96))))
        assert pc.tiling_problems(ok) == []
        bad = pc.KernelPlan(kernel="toy", grid=(1,), blocks=(
            pc.Block("lanes", (8, 96), array=(8, 192)),))
        assert "multiple of 128" in pc.tiling_problems(bad)[0]

    def test_divisibility_violation(self):
        plan = pc.KernelPlan(
            kernel="toy", grid=(1,),
            blocks=(pc.Block("a", (8, 8)),),
            tiled_axes=(("M", 100, 128),))
        with pytest.raises(pc.PallasBudgetError, match="not divisible"):
            pc.check_plan(plan)

    def test_block_bytes_and_kinds(self):
        assert pc.Block("a", (256, 512)).bytes == 256 * 512 * 4
        assert pc.Block("b", (4,), dtype="bfloat16").bytes == 8
        with pytest.raises(ValueError, match="kind"):
            pc.Block("c", (1,), kind="mystery")

    def test_odm_grad_shrink_policy_fits_all_widths(self):
        from repro.kernels import ops
        for d in (512, 1024, 2048, 4096, 8192, 16384):
            bm = ops._shrink_bm(512, 1 << 20, d)
            pc.check_plan(pc.odm_grad_plan(M=1 << 20, d=d, bm=bm))


# ---------------------------------------------------------------------------
# the declared-invariant battery
# ---------------------------------------------------------------------------

_ALL = inv.invariants()


class TestInvariantRegistry:
    def test_duplicate_declaration_raises(self):
        existing = _ALL[0]
        with pytest.raises(ValueError, match="already declared"):
            inv.declare(existing)

    def test_unknown_name_lists_declared(self):
        with pytest.raises(KeyError, match="no invariant"):
            inv.get("kernels.nope.never")

    def test_counters_are_shared_objects(self):
        """The modules' count functions read the registry's counters."""
        from repro.core import dsvrg, sodm
        for name, count in (("dsvrg.epoch_trace", dsvrg.epoch_trace_count),
                            ("sodm.perm_gather", sodm.perm_gather_count),
                            ("sodm.level_solve", sodm.level_solve_count)):
            c = inv.counter(name)
            n = count()
            assert n == c.count, name
            c.bump(("probe", None))
            assert count() == n + 1, name

    def test_every_kernel_and_route_is_covered(self):
        """Meta-acceptance: each registered Pallas kernel, each training
        route, AND each fault-tolerance/observability component has >= 1
        declared invariant."""
        kernels = {i.subject for i in _ALL if i.kind == "kernel"}
        assert kernels == set(pc.PLAN_BUILDERS), (
            f"kernels missing a declared invariant: "
            f"{set(pc.PLAN_BUILDERS) - kernels}")
        routes = {i.subject for i in _ALL if i.kind == "route"}
        assert routes == set(registry.routes()), (
            f"routes missing a declared invariant: "
            f"{set(registry.routes()) - routes}")
        comps = {i.subject for i in _ALL if i.kind == "component"}
        assert comps == set(inv.COMPONENTS), (
            f"components missing a declared invariant: "
            f"{set(inv.COMPONENTS) - comps}")


@pytest.mark.parametrize(
    "name", [i.name for i in _ALL if not i.slow])
def test_invariant(name):
    """The uniform battery: every quick declared invariant verifies."""
    inv.verify(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", [i.name for i in _ALL if i.slow])
def test_invariant_slow(name):
    inv.verify(name)


# ---------------------------------------------------------------------------
# boundary lint: fixtures fail, the tree passes
# ---------------------------------------------------------------------------

def _run_lint(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint.py"), *args],
        capture_output=True, text=True, cwd=ROOT, timeout=300)


class TestBoundaryLint:
    def test_tile_literal_fixture_fails(self):
        proc = _run_lint(os.path.join(FIXTURES, "bad_tile_literal.py"))
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert proc.stdout.count("T001") == 2, proc.stdout
        # the config-constructor exemption: SODMConfig(block=512) is fine
        assert "SODMConfig" not in proc.stdout

    def test_real_tree_is_clean(self):
        """Acceptance: scripts/lint.py exits 0 on the shipped tree."""
        proc = _run_lint()
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_warn_and_pallas_rules_inside_repro(self):
        """P001 applies under src/repro outside kernels/ — checked via the
        library API with an in-repro virtual path (the fixture never
        ships there)."""
        with open(os.path.join(FIXTURES, "bad_warn.py")) as fh:
            src = fh.read()
        got = bl.lint_file("src/repro/serve/bad_warn.py", source=src)
        codes = sorted(v.code for v in got)
        assert codes == ["P001"], got
        # the same file under kernels/ may import pallas
        got_k = bl.lint_file("src/repro/kernels/bad_warn.py", source=src)
        assert got_k == [], got_k

    def test_pragma_suppression(self):
        src = ("from repro.kernels import ops\n"
               "ops.decision_scores(1, 2, 3, 4, bt=512)"
               "  # lint: ignore[T001]\n")
        assert bl.lint_file("benchmarks/x.py", source=src) == []
        src_allow = "# lint: allow[T001]\n" + src.replace(
            "  # lint: ignore[T001]", "")
        assert bl.lint_file("benchmarks/x.py", source=src_allow) == []

    def test_list_rules(self):
        proc = _run_lint("--list-rules")
        assert proc.returncode == 0
        for code in bl.RULES:
            assert code in proc.stdout


# ---------------------------------------------------------------------------
# layering: a lower layer never imports a higher one
# ---------------------------------------------------------------------------

def _imported_modules(path):
    """Every module a file imports, function-level imports included."""
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("layer,higher", [
    ("core", "api"), ("kernels", "core"), ("kernels", "api"),
    ("data", "api"), ("serve", "api"), ("observe", "api"),
    ("distributed", "api")])
def test_lower_layers_import_no_higher(layer, higher):
    banned = f"repro.{higher}"
    base = os.path.join(ROOT, "src", "repro", layer)
    bad = []
    for dirpath, _, files in os.walk(base):
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                bad += [f"{os.path.relpath(path, ROOT)}: {mod}"
                        for mod in _imported_modules(path)
                        if mod == banned or mod.startswith(banned + ".")]
    assert not bad, bad


# ---------------------------------------------------------------------------
# count_pallas_calls migration: cache-warm counting stays exact
# ---------------------------------------------------------------------------

class TestLaunchCounterMigration:
    def test_warm_trace_cache_does_not_undercount(self):
        """The old monkeypatch counter needed clear_cache() before every
        count; the jaxpr walker must be exact on a WARM cache."""
        from repro.kernels import score
        x = jnp.ones((16, 8))
        c = jnp.ones((16,))
        thunk = lambda: score.score_tiles(x, x, c, kind="rbf", gamma=0.5,
                                          bt=8, bs=8, bd=8, interpret=True)
        jax.block_until_ready(thunk())       # warm the trace cache
        from repro.kernels import ops
        assert ops.count_pallas_calls(thunk) == 1
        assert ops.count_pallas_calls(thunk) == 1   # and stays exact
