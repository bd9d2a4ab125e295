"""Compiled-program introspection — what each XLA round program actually is.

The observability PRs so far measure the round loop from the *outside*
(wall clocks, fences, compile counters) and from *inside the graph*
(RoundTelemetry). What's still missing is the compiled program itself: how
many FLOPs does one ``fit_round`` executable perform, how many HBM bytes
does it touch, how much device memory does it pin — the per-program
accounting FedJAX (arXiv:2108.02117) treats as table stakes for credible
JAX FL simulation, and the numbers the sharding roadmap (arXiv:2004.13336)
needs before splitting those programs across replicas.

XLA exposes both through the AOT API at **build time** — zero per-round
cost, and once per compiled program: the introspector remembers each
program's report and records it again, without lowering or compiling
anything, when it is asked about the same program (a second ``fit()`` on one
simulation):

- ``compiled.cost_analysis()``: flops, transcendentals, bytes accessed;
- ``compiled.memory_analysis()``: argument/output/temp/generated-code
  bytes (the program's device-memory footprint).

:class:`ProgramIntrospector` wraps ``jit.lower(...).compile()`` around
abstract (``ShapeDtypeStruct``) arguments, times the compile, attributes
persistent-cache hits/misses via the counters the installed
:class:`~fl4health_tpu.observability.jaxmon.CompileMonitor` already
maintains, and lands each :class:`ProgramReport` in the metrics registry
(``fl_program_*`` gauges labeled by program), the JSONL event log (one
``program`` event, rendered by ``tools/perf_report.py``), and the
``fl_hbm_headroom_bytes`` gauge (device memory minus the largest program
footprint — how much model growth fits before the next OOM).

From a report plus a measured round wall time, measured MFU is
``flops / wall / peak`` — a hardware-grounded number, unlike the analytic
formula ``bench.py`` used to report. Caveat carried over from the flash
work: a Pallas custom call's FLOPs are invisible to ``cost_analysis`` —
the analytic numerator stays the honest one for those configs.
"""

from __future__ import annotations

import dataclasses
import logging
import time
import weakref
from typing import Any

from fl4health_tpu.observability import device_specs
from fl4health_tpu.observability.registry import MetricsRegistry

logger = logging.getLogger(__name__)

_CACHE_HITS = "jax_persistent_cache_hits_total"
_CACHE_MISSES = "jax_persistent_cache_misses_total"


@dataclasses.dataclass
class ProgramReport:
    """One compiled XLA program's cost/memory/compile accounting.

    ``None`` fields mean the backend did not expose that analysis — callers
    must propagate the absence (a ``null`` in artifacts), never substitute
    a zero that reads as "measured: nothing"."""

    name: str
    backend: str
    device_kind: str
    # cost_analysis — WHOLE-program logical work: XLA reports per-partition
    # numbers for SPMD-partitioned (mesh) executables, so capture scales
    # them by the partition count (collective traffic is not modeled; the
    # scaled bytes are an approximation)
    flops: float | None = None
    transcendentals: float | None = None
    bytes_accessed: float | None = None
    # memory_analysis (device-memory footprint components) — deliberately
    # PER-PARTITION on a mesh: peak_hbm_bytes is each chip's footprint,
    # which is what HBM-headroom accounting needs
    argument_bytes: int | None = None
    output_bytes: int | None = None
    temp_bytes: int | None = None
    generated_code_bytes: int | None = None
    # compile accounting
    compile_seconds: float | None = None
    cache_hits: int = 0
    cache_misses: int = 0
    # a multi-round scan program executes this many rounds per dispatch
    rounds_per_dispatch: int = 1
    # cohort-draw site of a registry program ("in_graph" for the chunked
    # cohort scan); None on dense / host-drawn programs (omitted from
    # as_dict/events like ``mesh``, so legacy program records keep their
    # exact shape)
    cohort_draw: str | None = None
    # mesh/sharding descriptor (parallel.program.RoundProgramBuilder
    # .descriptor()) when the program was built for a device mesh; None on
    # single-chip builds (and omitted from as_dict/events, so legacy
    # program records keep their exact shape)
    mesh: dict | None = None
    # precision-policy descriptor (precision.PrecisionConfig.describe())
    # when the program was compiled under an active mixed-precision policy;
    # None on f32 builds (omitted from as_dict/events like ``mesh``) — the
    # dtype a program's flops/MFU numbers are attributable to
    precision: dict | None = None

    @property
    def peak_hbm_bytes(self) -> int | None:
        """Conservative device-memory footprint of one dispatch: arguments
        + outputs + temporaries + generated code. Donated (aliased) buffers
        are counted on the argument side, so this is an upper bound."""
        parts = (self.argument_bytes, self.output_bytes, self.temp_bytes,
                 self.generated_code_bytes)
        if all(p is None for p in parts):
            return None
        return int(sum(p or 0 for p in parts))

    @property
    def flops_per_round(self) -> float | None:
        if self.flops is None:
            return None
        return self.flops / max(self.rounds_per_dispatch, 1)

    @property
    def cache_hit(self) -> bool | None:
        """True when the compile was served from the persistent cache,
        False on a real backend compile, None when no cache event fired
        (cache disabled, or the in-memory jit cache absorbed it)."""
        if self.cache_hits == 0 and self.cache_misses == 0:
            return None
        return self.cache_misses == 0

    def roofline(self) -> dict | None:
        return device_specs.roofline(self.flops, self.bytes_accessed,
                                     self.device_kind)

    def as_dict(self) -> dict[str, Any]:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        if d.get("mesh") is None:
            del d["mesh"]
        if d.get("precision") is None:
            del d["precision"]
        if d.get("cohort_draw") is None:
            del d["cohort_draw"]
        d["peak_hbm_bytes"] = self.peak_hbm_bytes
        d["cache_hit"] = self.cache_hit
        roof = self.roofline()
        if roof:
            d["roofline"] = roof
        return d


def analyze_compiled(compiled: Any, n_partitions: int = 1) -> dict[str, Any]:
    """Extract cost/memory analysis from a ``jax`` compiled executable,
    defensively: backends without a cost model yield ``None`` fields, never
    an exception (the caller may be mid-``fit``).

    ``n_partitions``: SPMD partition count of the executable (the mesh's
    device count). XLA's ``cost_analysis()`` reports ONE partition's
    flops/transcendentals/bytes for a partitioned program, so they are
    scaled back up to whole-program numbers here — otherwise every
    downstream per-chip division (MFU, tflops_per_chip) would divide by
    the device count a second time. ``memory_analysis`` is left
    per-partition on purpose (each chip's footprint)."""
    out: dict[str, Any] = {
        "flops": None, "transcendentals": None, "bytes_accessed": None,
        "argument_bytes": None, "output_bytes": None, "temp_bytes": None,
        "generated_code_bytes": None,
    }
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            for field, key in (("flops", "flops"),
                               ("transcendentals", "transcendentals"),
                               ("bytes_accessed", "bytes accessed")):
                if key in cost:
                    out[field] = float(cost[key]) * max(n_partitions, 1)
    except Exception:
        logger.debug("cost_analysis unavailable", exc_info=True)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["argument_bytes"] = int(mem.argument_size_in_bytes)
            out["output_bytes"] = int(mem.output_size_in_bytes)
            out["temp_bytes"] = int(mem.temp_size_in_bytes)
            out["generated_code_bytes"] = int(mem.generated_code_size_in_bytes)
    except Exception:
        logger.debug("memory_analysis unavailable", exc_info=True)
    return out


def abstractify(tree: Any) -> Any:
    """Concrete arrays -> ``ShapeDtypeStruct`` leaves, so ``jit.lower``
    traces without touching (or allocating on) the device. Leaves that are
    already abstract pass through."""
    import jax
    import jax.numpy as jnp

    def to_sds(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

    return jax.tree_util.tree_map(to_sds, tree)


class ProgramIntrospector:
    """Collects :class:`ProgramReport`\\ s for a run's compiled programs.

    One instance per :class:`~fl4health_tpu.observability.Observability`
    handle; reports accumulate in ``.reports`` (last introspection of a
    name wins) and every capture lands in the registry + JSONL log.

    A report is a pure function of the compiled program, so each name's
    last capture is remembered with what identifies that program: the
    jitted object itself (through a weak reference: a dead one matches
    nothing, so an ``id`` cannot be reused, and the entry keeps neither
    the function nor the simulation its closure holds alive), the
    abstract arguments it was lowered against, ``rounds_per_dispatch``,
    ``mesh``, ``precision`` and ``cohort_draw``. Asked for the same
    program again, ``introspect_jit`` records the remembered report and
    does no other work; anything else differing is a miss that runs the
    capture and replaces the entry. Only the report is kept — never the
    executable, its HLO text or an array.
    ``hits`` / ``misses`` count both outcomes, as does
    ``fl_program_introspections_total{program, result}``."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.reports: dict[str, ProgramReport] = {}
        # name -> (weak ref to the jitted object, key, report) of that
        # name's last capture
        self._remembered: dict[
            str, tuple[weakref.ref, tuple, ProgramReport]] = {}
        self.hits = 0
        self.misses = 0

    # -- capture ---------------------------------------------------------
    def introspect_jit(self, name: str, jitted: Any, args: tuple,
                       rounds_per_dispatch: int = 1,
                       mesh: dict | None = None,
                       precision: dict | None = None,
                       cohort_draw: str | None = None
                       ) -> ProgramReport | None:
        """AOT-lower and compile ``jitted`` against (abstracted) ``args``
        and record the report — or, when this name's last capture was of
        the same program (class docstring), record that report again and
        touch nothing else: its ``compile_seconds`` and cache counts stay
        those of the capture. The compile goes through XLA's normal
        ``compile_or_get_cached`` path, so with the persistent compilation
        cache enabled the later jit dispatch of the SAME program is a disk
        hit, not a second backend compile. Returns None (after logging) on
        any failure — introspection must never take down a run — and a
        failure is not remembered."""
        import jax

        try:
            abstract = abstractify(args)
            leaves, treedef = jax.tree_util.tree_flatten(abstract)
            key = (treedef, tuple(leaves), rounds_per_dispatch, mesh,
                   precision, cohort_draw)
            jitted_ref = weakref.ref(jitted)
            last = self._remembered.get(name)
            hit = (last is not None and last[0]() is jitted
                   and last[1] == key)
            self._count(name, hit)
            if hit:
                return self.record(last[2])
            hits0 = self.registry.counter(_CACHE_HITS).value
            misses0 = self.registry.counter(_CACHE_MISSES).value
            t0 = time.perf_counter()
            compiled = jitted.lower(*abstract).compile()
            compile_s = time.perf_counter() - t0
            d = jax.devices()[0]
            report = ProgramReport(
                name=name,
                backend=d.platform,
                device_kind=getattr(d, "device_kind", "unknown"),
                compile_seconds=compile_s,
                cache_hits=int(self.registry.counter(_CACHE_HITS).value - hits0),
                cache_misses=int(
                    self.registry.counter(_CACHE_MISSES).value - misses0
                ),
                rounds_per_dispatch=rounds_per_dispatch,
                cohort_draw=cohort_draw,
                mesh=mesh,
                precision=precision,
                **analyze_compiled(
                    compiled,
                    n_partitions=int((mesh or {}).get("n_devices", 1)),
                ),
            )
        except Exception:
            logger.warning("program introspection failed for %r", name,
                           exc_info=True)
            return None
        self._remembered[name] = (jitted_ref, key, report)
        return self.record(report)

    def _count(self, name: str, hit: bool) -> None:
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        self.registry.counter(
            "fl_program_introspections_total",
            help="introspect_jit calls: hit = the remembered report of the "
                 "same compiled program was recorded again, miss = the "
                 "program was lowered, compiled and analysed",
            labels={"program": name, "result": "hit" if hit else "miss"},
        ).inc()

    def record(self, report: ProgramReport) -> ProgramReport:
        """Register a report's numbers as ``fl_program_*`` gauges (labeled
        by program) plus one ``program`` JSONL event."""
        self.reports[report.name] = report
        reg = self.registry
        labels = {"program": report.name}
        gauges = (
            ("fl_program_flops",
             "XLA cost-model FLOPs of one compiled dispatch", report.flops),
            ("fl_program_bytes_accessed",
             "XLA cost-model bytes accessed by one dispatch",
             report.bytes_accessed),
            ("fl_program_transcendentals",
             "XLA cost-model transcendental ops per dispatch",
             report.transcendentals),
            ("fl_program_hbm_peak_bytes",
             "program device-memory footprint (args+outputs+temps+code)",
             report.peak_hbm_bytes),
            ("fl_program_compile_seconds",
             "wall time of this program's lower+compile",
             report.compile_seconds),
        )
        for gname, ghelp, value in gauges:
            if value is not None:
                reg.gauge(gname, help=ghelp, labels=labels).set(float(value))
        reg.log_event("program", **report.as_dict())
        return report

    # -- derived numbers -------------------------------------------------
    def max_program_footprint(self) -> int | None:
        peaks = [r.peak_hbm_bytes for r in self.reports.values()
                 if r.peak_hbm_bytes is not None]
        return max(peaks) if peaks else None

    def hbm_headroom_bytes(self, device=None) -> int | None:
        """Device memory minus the largest program footprint — how much
        bigger the next model/cohort can get before OOM. Sets the
        ``fl_hbm_headroom_bytes`` gauge when computable (needs both a known
        device capacity and at least one memory-analyzed program)."""
        footprint = self.max_program_footprint()
        total = device_specs.device_memory_bytes(device)
        if footprint is None or total is None:
            return None
        headroom = int(total - footprint)
        self.registry.gauge(
            "fl_hbm_headroom_bytes",
            help="device memory minus peak compiled-program footprint",
        ).set(headroom)
        return headroom

    def round_flops(self, names: tuple[str, ...]) -> float | None:
        """Sum of per-round FLOPs over the named programs (the ones one
        federated round dispatches); None when none were cost-analyzed."""
        vals = [self.reports[n].flops_per_round for n in names
                if n in self.reports
                and self.reports[n].flops_per_round is not None]
        return sum(vals) if vals else None

    def clear(self) -> None:
        self.reports.clear()
        self._remembered.clear()
