"""HLO analysis: collective-bytes parser + trip-aware dot FLOPs counter,
validated against modules with KNOWN flops/collectives."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as ha


class TestShapeBytes:
    def test_simple(self):
        assert ha.shape_bytes("f32[16,4096,576]") == 16 * 4096 * 576 * 4
        assert ha.shape_bytes("bf16[8]") == 16

    def test_tuple(self):
        s = "(f32[4,4]{1,0}, bf16[2]{0})"
        assert ha.shape_bytes(s) == 64 + 4

    def test_non_numeric_ignored(self):
        assert ha.shape_bytes("token[]") == 0


class TestDotFlops:
    def test_plain_matmul(self):
        M = N = K = 64
        f = jax.jit(lambda a, b: a @ b)
        hlo = f.lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                      jax.ShapeDtypeStruct((K, N), jnp.float32)) \
            .compile().as_text()
        got = ha.dot_flops(hlo)
        assert got == 2 * M * N * K, got

    def test_scan_multiplies_trip_count(self):
        """A matmul inside lax.scan must count trip-count times."""
        M = 32
        TRIPS = 7

        def f(a, b):
            def body(c, _):
                return c @ b, None
            c, _ = jax.lax.scan(body, a, None, length=TRIPS)
            return c

        hlo = jax.jit(f).lower(
            jax.ShapeDtypeStruct((M, M), jnp.float32),
            jax.ShapeDtypeStruct((M, M), jnp.float32)).compile().as_text()
        got = ha.dot_flops(hlo)
        want = 2 * M * M * M * TRIPS
        assert got == want, (got, want)

    def test_xla_cost_analysis_undercounts_scan(self):
        """Documents WHY dot_flops exists: XLA counts the body once."""
        M, TRIPS = 32, 7

        def f(a, b):
            def body(c, _):
                return c @ b, None
            c, _ = jax.lax.scan(body, a, None, length=TRIPS)
            return c

        comp = jax.jit(f).lower(
            jax.ShapeDtypeStruct((M, M), jnp.float32),
            jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
        # ha.xla_flops normalizes the list-vs-dict cost_analysis() return
        # across jax versions
        xla_flops = ha.xla_flops(comp)
        assert xla_flops < 2 * M ** 3 * TRIPS  # undercounted


class TestWireBytes:
    def test_conventions(self):
        b = 1024
        assert ha._wire_bytes("all-gather", b, 4) == b * 3 / 4
        assert ha._wire_bytes("all-reduce", b, 4) == 2 * b * 3 / 4
        assert ha._wire_bytes("reduce-scatter", b, 4) == b * 3
        assert ha._wire_bytes("collective-permute", b, 4) == b
        assert ha._wire_bytes("all-reduce", b, 1) == 0.0


class TestCollectiveParse:
    def test_synthetic_module(self):
        hlo = """HloModule test

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ag = f32[32]{0} all-gather(%x), channel_id=1, replica_groups=[2,4]<=[8], dimensions={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %x)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %ar = f32[8]{0} all-reduce(%a), channel_id=2, replica_groups=[1,8]<=[8], to_apply=%add
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""
        stats = ha.collective_bytes(hlo)
        # all-reduce: 8 floats = 32B, g=8 -> 2*32*7/8 = 56
        assert stats.bytes_by_kind["all-reduce"] == pytest.approx(56.0)
        # all-gather inside while x5 trips: result 32 floats = 128B, g=4
        # -> 5 * 128 * 3/4 = 480
        assert stats.bytes_by_kind["all-gather"] == pytest.approx(480.0)
        assert stats.count_by_kind["all-gather"] == 5


class TestEntryName:
    def test_dotted_and_prefixed_names(self):
        assert ha._entry_name("ENTRY %main.42 (a: f32[4]) -> f32[4] {") \
            == "main.42"
        assert ha._entry_name("ENTRY main (a: f32[4]) -> f32[4] {") == "main"
        assert ha._entry_name("HloModule m\n\nENTRY %jit_f.7 (x) -> f32 {") \
            == "jit_f.7"

    def test_missing_entry_returns_none(self):
        assert ha._entry_name("%helper (p: f32[4]) -> f32[4] {") is None

    def test_missing_entry_falls_back_to_whole_text(self):
        """Without an ENTRY header the whole text is one computation and
        top-level collectives still count (multiplicity 1)."""
        hlo = ("%ar = f32[8]{0} all-reduce(%a), channel_id=1, "
               "replica_groups=[1,8]<=[8], to_apply=%add\n")
        stats = ha.collective_bytes(hlo)
        assert stats.count_by_kind["all-reduce"] == 1
        assert stats.bytes_by_kind["all-reduce"] == pytest.approx(56.0)


class TestGroupSize:
    def test_strided_form(self):
        assert ha._group_size("... replica_groups=[2,4]<=[8] ...") == 4

    def test_explicit_group_list(self):
        assert ha._group_size("... replica_groups={{0,1},{2,3}} ...") == 2
        assert ha._group_size("... replica_groups={{0,1,2,3}} ...") == 4

    def test_default_when_absent(self):
        assert ha._group_size("%ag = f32[8] all-gather(%x)") == 2


_NESTED_WHILE_HLO = """HloModule nested

%icond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(4)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%ibody (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = f32[8]{0} all-reduce(%x), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
}

%ocond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %j = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}

%obody (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %w = (s32[], f32[8]) while(%p), condition=%icond, body=%ibody
  ROOT %t = (s32[], f32[8]) tuple(%w)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %w = (s32[], f32[8]) while(%init), condition=%ocond, body=%obody
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""


class TestMultiplicity:
    def test_nested_while_multiplies(self):
        """Outer 3 trips x inner 4 trips = 12 executions of the inner
        body's all-reduce."""
        stats = ha.collective_bytes(_NESTED_WHILE_HLO)
        assert stats.count_by_kind["all-reduce"] == 12
        # 32B result, g=8 -> 2*32*7/8 = 56 per execution
        assert stats.bytes_by_kind["all-reduce"] == pytest.approx(12 * 56.0)

    def test_known_trip_count_annotation_wins(self):
        """XLA's backend_config trip annotation overrides the parsed
        compare-constant (here deliberately different: 2 vs 9)."""
        hlo = """HloModule annotated

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(2)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ag = f32[32]{0} all-gather(%x), channel_id=1, replica_groups=[2,4]<=[8], dimensions={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %x)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"9"}}
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""
        stats = ha.collective_bytes(hlo)
        assert stats.count_by_kind["all-gather"] == 9
        assert stats.bytes_by_kind["all-gather"] == \
            pytest.approx(9 * 128 * 3 / 4)

    def test_called_computation_inherits_caller_count(self):
        """A collective inside a computation reached via to_apply= is
        charged once per call site (twice here)."""
        hlo = """HloModule called

%helper (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %ar = f32[8]{0} all-reduce(%p), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %c1 = f32[8] call(%a), to_apply=%helper
  %c2 = f32[8] call(%c1), to_apply=%helper
  ROOT %r = f32[8] add(%c1, %c2)
}
"""
        stats = ha.collective_bytes(hlo)
        assert stats.count_by_kind["all-reduce"] == 2
        assert stats.bytes_by_kind["all-reduce"] == pytest.approx(2 * 56.0)

    def test_count_by_kind_attribution(self):
        """Mixed kinds attribute independently: explicit-group all-gather
        (g=2) at entry + permute, with per-kind byte accounting."""
        hlo = """HloModule mixed

ENTRY %main (a: f32[8]) -> f32[16] {
  %a = f32[8] parameter(0)
  %ag = f32[16]{0} all-gather(%a), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}
  %cp = f32[8]{0} collective-permute(%a), channel_id=2, source_target_pairs={{0,1},{1,0}}
  ROOT %r = f32[16] add(%ag, %ag)
}
"""
        stats = ha.collective_bytes(hlo)
        assert stats.count_by_kind == {"all-gather": 1,
                                       "collective-permute": 1}
        # all-gather: 64B result, g=2 -> 32; permute: full 32B payload
        assert stats.bytes_by_kind["all-gather"] == pytest.approx(32.0)
        assert stats.bytes_by_kind["collective-permute"] == \
            pytest.approx(32.0)


class TestRoofline:
    def test_terms_and_dominance(self):
        v5e = ha.hardware("TPU v5 lite")
        rl = ha.roofline(197e12, 819e9, 0.0, v5e)  # 1s compute, 1s memory
        assert rl.compute_s == pytest.approx(1.0)
        assert rl.memory_s == pytest.approx(1.0)
        assert rl.collective_s == 0.0
        rl2 = ha.roofline(1e12, 1e9, 500e9, v5e)
        assert rl2.dominant == "collective"

    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="no published peaks"):
            ha.hardware("cpu")

    def test_dryrun_cell_on_host_devices(self, monkeypatch):
        """A dry-run cell compiled on host CPU devices gets the roofline of
        the chip the production mesh stands for."""
        import importlib
        import os
        from repro import configs
        from repro.configs.base import ShapeConfig
        from repro.launch import mesh as mesh_mod
        from repro.sharding import make_mesh
        jax.devices()                 # this process's device count is fixed
        # importing the dry run prepends a device count to XLA_FLAGS
        monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
        dryrun = importlib.import_module("repro.launch.dryrun")
        monkeypatch.setattr(configs, "get", configs.get_smoke)
        monkeypatch.setattr(configs, "get_shape",
                            lambda name: ShapeConfig(name, 32, 2, "train"))
        monkeypatch.setattr(
            mesh_mod, "make_production_mesh",
            lambda multi_pod=False: make_mesh((1, 1), ("data", "model")))
        out = dryrun._cell("smollm-135m", "train_tiny", False)
        assert out["status"] == "ok"
        hw = ha.hardware(mesh_mod.PRODUCTION_DEVICE_KIND)
        assert out["roofline"]["compute_s"] == pytest.approx(
            out["cost"]["flops_per_device"] / hw.peak_flops)
        assert out["roofline"]["memory_s"] == pytest.approx(
            out["cost"]["hbm_bytes_per_device"] / hw.hbm_bw)
