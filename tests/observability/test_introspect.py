"""Compiled-program introspection: XLA cost/memory analysis capture,
registry/JSONL recording, HBM headroom, and the device spec table."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from fl4health_tpu.observability import device_specs
from fl4health_tpu.observability.introspect import (
    ProgramIntrospector,
    ProgramReport,
    abstractify,
    analyze_compiled,
)
from fl4health_tpu.observability.registry import MetricsRegistry


def _matmul_jit():
    return jax.jit(lambda a, b: (a @ b, jnp.sin(a).sum()))


class TestAnalyzeCompiled:
    def test_cost_and_memory_fields(self):
        f = _matmul_jit()
        sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        out = analyze_compiled(f.lower(sds, sds).compile())
        # 64^3 * 2 matmul FLOPs plus the sin/sum tail
        assert out["flops"] >= 2 * 64**3
        assert out["bytes_accessed"] > 0
        assert out["transcendentals"] >= 64 * 64  # the sin
        assert out["argument_bytes"] == 2 * 64 * 64 * 4
        assert out["temp_bytes"] is not None

    @pytest.mark.multichip
    def test_partitioned_flops_scaled_to_whole_program(self):
        """XLA's cost_analysis reports ONE partition's FLOPs for an SPMD
        executable; capture must scale them back to whole-program numbers
        or every downstream per-chip division (MFU, tflops_per_chip)
        divides by the device count twice."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        fn = lambda a, b: a @ b  # noqa: E731
        sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        plain = analyze_compiled(jax.jit(fn).lower(sds, sds).compile())
        mesh = Mesh(devs[:8], ("clients",))
        sh = NamedSharding(mesh, P("clients"))
        sharded_exe = jax.jit(
            fn, in_shardings=(sh, None), out_shardings=sh
        ).lower(sds, sds).compile()
        raw = analyze_compiled(sharded_exe)
        scaled = analyze_compiled(sharded_exe, n_partitions=8)
        # this jaxlib reports per-partition numbers; the scaled capture
        # must land back on the whole-program count
        assert raw["flops"] == pytest.approx(plain["flops"] / 8)
        assert scaled["flops"] == pytest.approx(plain["flops"])

        # introspect_jit applies the scaling from the mesh descriptor
        intro = ProgramIntrospector(MetricsRegistry())
        rep = intro.introspect_jit(
            "sharded_mm",
            jax.jit(fn, in_shardings=(sh, None), out_shardings=sh),
            (sds, sds), mesh={"n_devices": 8, "axes": {"clients": 8}},
        )
        assert rep.flops == pytest.approx(plain["flops"])

    def test_broken_executable_degrades_to_none(self):
        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no cost model")

            def memory_analysis(self):
                raise RuntimeError("no memory model")

        out = analyze_compiled(Broken())
        assert all(v is None for v in out.values())


class TestAbstractify:
    def test_arrays_become_shape_dtype_structs(self):
        tree = {"a": jnp.ones((2, 3)), "b": [jnp.zeros(4, jnp.int32)]}
        sds = abstractify(tree)
        assert sds["a"] == jax.ShapeDtypeStruct((2, 3), jnp.float32)
        assert sds["b"][0].dtype == jnp.int32

    def test_existing_sds_pass_through(self):
        s = jax.ShapeDtypeStruct((5,), jnp.float32)
        assert abstractify((s,))[0] is s


class TestProgramIntrospector:
    def test_introspect_jit_records_report_gauges_and_event(self):
        reg = MetricsRegistry()
        intro = ProgramIntrospector(reg)
        f = _matmul_jit()
        x = jnp.ones((32, 32))
        rep = intro.introspect_jit("mm", f, (x, x))
        assert rep is not None and rep.name == "mm"
        assert rep.flops and rep.flops >= 2 * 32**3
        assert rep.compile_seconds > 0
        assert rep.peak_hbm_bytes and rep.peak_hbm_bytes > 0
        snap = reg.snapshot()
        assert snap["fl_program_flops"]['{program="mm"}'] == rep.flops
        assert (snap["fl_program_hbm_peak_bytes"]['{program="mm"}']
                == rep.peak_hbm_bytes)
        events = [e for e in reg.events if e["event"] == "program"]
        assert len(events) == 1 and events[0]["name"] == "mm"
        assert events[0]["peak_hbm_bytes"] == rep.peak_hbm_bytes

    def test_introspection_failure_returns_none_not_raise(self):
        intro = ProgramIntrospector(MetricsRegistry())
        assert intro.introspect_jit("bad", object(), (jnp.ones(2),)) is None

    def test_failure_is_not_remembered(self):
        intro = ProgramIntrospector(MetricsRegistry())
        x = jnp.ones((8, 8))
        assert intro.introspect_jit("p", object(), (x, x)) is None
        assert intro._remembered == {} and intro.reports == {}
        # ... and does not displace a sound entry of the same name
        f = _CountingJit()
        rep = intro.introspect_jit("p", f, (x, x))
        assert intro.introspect_jit("p", object(), (x, x)) is None
        assert intro.introspect_jit("p", f, (x, x)) is rep
        assert f.lowers == 1

    def test_round_flops_sums_per_round(self):
        reg = MetricsRegistry()
        intro = ProgramIntrospector(reg)
        intro.record(ProgramReport("fit", "cpu", "cpu", flops=100.0))
        intro.record(ProgramReport("eval", "cpu", "cpu", flops=20.0))
        intro.record(ProgramReport("chunk", "cpu", "cpu", flops=1000.0,
                                   rounds_per_dispatch=10))
        assert intro.round_flops(("fit", "eval")) == 120.0
        assert intro.round_flops(("chunk",)) == 100.0
        # missing / cost-model-less programs contribute nothing
        assert intro.round_flops(("nope",)) is None
        intro.record(ProgramReport("nocost", "cpu", "cpu"))
        assert intro.round_flops(("nocost",)) is None

    def test_hbm_headroom_none_on_cpu_gauge_set_when_known(self, monkeypatch):
        reg = MetricsRegistry()
        intro = ProgramIntrospector(reg)
        intro.record(ProgramReport("p", "cpu", "cpu", argument_bytes=100,
                                   output_bytes=50, temp_bytes=25,
                                   generated_code_bytes=0))
        # CPU exposes no memory_stats and has no spec entry
        assert intro.hbm_headroom_bytes() is None
        assert "fl_hbm_headroom_bytes" not in reg.snapshot()
        monkeypatch.setattr(device_specs, "device_memory_bytes",
                            lambda device=None: 1000)
        assert intro.hbm_headroom_bytes() == 1000 - 175
        assert reg.snapshot()["fl_hbm_headroom_bytes"] == 825.0


class _CountingJit:
    """A jitted function whose ``.lower`` calls are counted — the memo's
    whole point is that a hit never reaches it."""

    def __init__(self, fn=None):
        self._jit = fn if fn is not None else _matmul_jit()
        self.lowers = 0

    def lower(self, *args, **kwargs):
        self.lowers += 1
        return self._jit.lower(*args, **kwargs)


def _introspections(reg, program):
    snap = reg.snapshot().get("fl_program_introspections_total", {})
    return {result: int(snap.get(f'{{program="{program}",result="{result}"}}', 0))
            for result in ("hit", "miss")}


class TestRememberedReports:
    """One capture per compiled program: asked again about the same jitted
    object, abstract arguments and descriptors, ``introspect_jit`` records
    the remembered report and lowers nothing."""

    def test_second_identical_call_hits_without_lowering(self):
        reg = MetricsRegistry()
        intro = ProgramIntrospector(reg)
        f = _CountingJit()
        x = jnp.ones((32, 32))
        first = intro.introspect_jit("mm", f, (x, x))
        assert f.lowers == 1 and _introspections(reg, "mm") == {
            "hit": 0, "miss": 1}
        # the record a fresh fit() would have to rebuild: reports, gauges
        intro.reports.clear()
        reg.gauge("fl_program_flops", labels={"program": "mm"}).set(-1.0)
        # concrete arrays or their ShapeDtypeStructs: the same program
        second = intro.introspect_jit("mm", f, abstractify((x, x)))
        assert f.lowers == 1
        assert second == first and intro.reports["mm"] == first
        assert second.compile_seconds == first.compile_seconds
        assert reg.snapshot()["fl_program_flops"]['{program="mm"}'] == first.flops
        events = [e for e in reg.events if e["event"] == "program"]
        assert len(events) == 2
        strip = lambda e: {k: v for k, v in e.items() if k != "ts"}  # noqa: E731
        assert strip(events[0]) == strip(events[1])
        assert _introspections(reg, "mm") == {"hit": 1, "miss": 1}
        assert (intro.hits, intro.misses) == (1, 1)

    @pytest.mark.parametrize("change", [
        "shape", "dtype", "weak_type", "tree", "jitted",
        "rounds_per_dispatch", "mesh", "precision", "cohort_draw",
    ])
    def test_any_difference_misses_and_replaces_the_entry(self, change):
        reg = MetricsRegistry()
        intro = ProgramIntrospector(reg)
        f = _CountingJit(jax.jit(lambda t: t["a"] @ t["b"]))
        x = jnp.ones((16, 16))
        args, kw, g = ({"a": x, "b": x},), {}, f
        first = intro.introspect_jit("p", f, args)
        if change == "shape":
            x2 = jnp.ones((8, 8))
            args = ({"a": x2, "b": x2},)
        elif change == "dtype":
            x2 = jnp.ones((16, 16), jnp.bfloat16)
            args = ({"a": x2, "b": x2},)
        elif change == "weak_type":
            weak = jax.ShapeDtypeStruct((16, 16), jnp.float32, weak_type=True)
            args = ({"a": weak, "b": x},)
        elif change == "tree":
            args = ({"a": x, "b": x, "c": x},)
        elif change == "jitted":
            g = _CountingJit(jax.jit(lambda t: t["a"] @ t["b"]))
        elif change == "rounds_per_dispatch":
            kw = {"rounds_per_dispatch": 4}
        elif change == "mesh":
            kw = {"mesh": {"n_devices": 1, "axes": {"clients": 1}}}
        elif change == "precision":
            kw = {"precision": {"compute_dtype": "bfloat16"}}
        elif change == "cohort_draw":
            kw = {"cohort_draw": "in_graph"}
        other = intro.introspect_jit("p", g, args, **kw)
        assert other is not None and other is not first
        assert sum(j.lowers for j in {f, g}) == 2
        assert _introspections(reg, "p") == {"hit": 0, "miss": 2}
        # the entry now describes the second program: it hits, the first
        # program misses again
        assert intro.introspect_jit("p", g, args, **kw) is other
        assert _introspections(reg, "p") == {"hit": 1, "miss": 2}
        intro.introspect_jit("p", f, ({"a": x, "b": x},))
        assert _introspections(reg, "p") == {"hit": 1, "miss": 3}
        assert sum(j.lowers for j in {f, g}) == 3

    @pytest.mark.multichip
    def test_leaf_sharding_is_part_of_the_program(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs 2 virtual devices")
        mesh = Mesh(devs[:2], ("clients",))
        f = _CountingJit(jax.jit(lambda a: a * 2.0))
        intro = ProgramIntrospector(MetricsRegistry())
        split, whole = (
            jax.ShapeDtypeStruct((8, 4), jnp.float32,
                                 sharding=NamedSharding(mesh, spec))
            for spec in (P("clients"), P())
        )
        intro.introspect_jit("p", f, (split,))
        intro.introspect_jit("p", f, (split,))
        assert (f.lowers, intro.hits) == (1, 1)
        intro.introspect_jit("p", f, (whole,))
        assert (f.lowers, intro.hits, intro.misses) == (2, 1, 2)

    def test_names_are_remembered_apart(self):
        """fit's eval program runs under two names (validation and test
        shapes): one jitted object, one entry per name."""
        intro = ProgramIntrospector(MetricsRegistry())
        f = _CountingJit()
        a, b = jnp.ones((8, 8)), jnp.ones((16, 16))
        intro.introspect_jit("eval", f, (a, a))
        intro.introspect_jit("eval_test", f, (b, b))
        intro.introspect_jit("eval", f, (a, a))
        intro.introspect_jit("eval_test", f, (b, b))
        assert f.lowers == 2 and (intro.hits, intro.misses) == (2, 2)

    def test_clear_forgets(self):
        intro = ProgramIntrospector(MetricsRegistry())
        f = _CountingJit()
        x = jnp.ones((8, 8))
        intro.introspect_jit("p", f, (x, x))
        intro.clear()
        assert intro.reports == {} and intro._remembered == {}
        intro.introspect_jit("p", f, (x, x))
        assert f.lowers == 2

    def test_entry_does_not_keep_the_program_alive(self):
        """A round program's closure holds its simulation (and through it
        the device state): an Observability handle that outlives the
        simulation must not pin it. A dead entry matches nothing."""
        import gc

        intro = ProgramIntrospector(MetricsRegistry())
        f = _CountingJit()
        x = jnp.ones((8, 8))
        intro.introspect_jit("p", f, (x, x))
        (jitted_ref, _, _), = intro._remembered.values()
        del f
        gc.collect()
        assert jitted_ref() is None
        g = _CountingJit()
        intro.introspect_jit("p", g, (x, x))
        assert g.lowers == 1 and (intro.hits, intro.misses) == (0, 2)

    def test_entry_holds_report_only(self):
        """No executable, no HLO text, no concrete array: the memo must not
        pin device memory or megabytes of text between fit() calls."""
        intro = ProgramIntrospector(MetricsRegistry())
        f = _CountingJit()
        x = jnp.ones((8, 8))
        rep = intro.introspect_jit("p", f, (x, x))
        (jitted_ref, key, report), = intro._remembered.values()
        assert jitted_ref() is f and report is rep

        def flat(v):
            if isinstance(v, (tuple, list)):
                for item in v:
                    yield from flat(item)
            elif isinstance(v, dict):
                yield from flat(list(v.values()))
            else:
                yield v

        for v in flat([key, dataclasses.asdict(report)]):
            assert not isinstance(v, (jax.Array, jax.stages.Compiled,
                                      jax.stages.Lowered))
            assert not (isinstance(v, str) and len(v) > 200)


class TestProgramReport:
    def test_peak_hbm_none_without_memory_analysis(self):
        rep = ProgramReport("p", "cpu", "cpu", flops=1.0)
        assert rep.peak_hbm_bytes is None

    def test_cache_hit_attribution(self):
        assert ProgramReport("p", "cpu", "cpu").cache_hit is None
        assert ProgramReport("p", "cpu", "cpu", cache_hits=1).cache_hit is True
        assert ProgramReport("p", "cpu", "cpu", cache_misses=1,
                             cache_hits=1).cache_hit is False

    def test_as_dict_carries_derived_fields(self):
        d = ProgramReport("p", "cpu", "TPU v4", flops=100.0,
                          bytes_accessed=10.0, argument_bytes=4,
                          output_bytes=4, temp_bytes=2,
                          generated_code_bytes=0).as_dict()
        assert d["peak_hbm_bytes"] == 10
        assert d["roofline"]["intensity_flops_per_byte"] == 10.0
        assert d["roofline"]["compute_bound"] is False  # 10 << v4 ridge


class TestDeviceSpecs:
    def test_alias_normalization(self):
        assert (device_specs.peak_bf16_flops("TPU v5 lite")
                == device_specs.peak_bf16_flops("TPU v5e"))
        assert device_specs.peak_bf16_flops("TPU v6 lite") == 918e12

    def test_unknown_kind_has_no_peak(self):
        assert device_specs.peak_bf16_flops("cpu") is None
        assert device_specs.peak_bf16_flops(None) is None
        assert device_specs.lookup("Quantum TPU v99") is None

    def test_mfu_pct(self):
        assert device_specs.mfu_pct(27.5e12, "TPU v4") == pytest.approx(10.0)
        assert device_specs.mfu_pct(1e12, "cpu") is None

    def test_roofline_ridge(self):
        r = device_specs.roofline(flops=1e12, bytes_accessed=1e9,
                                  device_kind="TPU v4")
        assert r["intensity_flops_per_byte"] == pytest.approx(1000.0)
        assert r["ridge_flops_per_byte"] == pytest.approx(275e12 / 1228e9)
        assert r["compute_bound"] is True
        assert device_specs.roofline(None, 1.0, "TPU v4") is None

    def test_device_memory_bytes_prefers_live_stats(self):
        class Dev:
            device_kind = "TPU v4"

            def memory_stats(self):
                return {"bytes_limit": 123}

        assert device_specs.device_memory_bytes(Dev()) == 123

        class SpecOnly:
            device_kind = "TPU v4"

            def memory_stats(self):
                return None

        assert (device_specs.device_memory_bytes(SpecOnly())
                == device_specs.DEVICE_SPECS["TPU v4"].hbm_bytes)
