"""Observability — round-level tracing, metrics, and XLA profiling hooks.

The reference's visibility story is wall-clock deltas in reporter dicts
(base_server.py fit/eval timing). Because the TPU build compiles a whole FL
round into two XLA programs, "where did the time go" needs three different
instruments, bundled here:

- :mod:`~fl4health_tpu.observability.spans` — nested context-manager spans
  on monotonic clocks, exported as Chrome trace-event JSON (open in
  Perfetto: one smoke run yields a visual per-round timeline of
  configure_fit -> fit_round -> aggregate -> eval_round -> checkpoint);
- :mod:`~fl4health_tpu.observability.registry` — process-wide
  counters/gauges/histograms with Prometheus text exposition and a JSONL
  event log (``tools/perf_report.py`` renders it);
- :mod:`~fl4health_tpu.observability.jaxmon` — JAX hooks: compile/cache
  event counting via ``jax.monitoring``, honest device-time fencing
  (``block_until_ready`` only when enabled), opt-in per-round
  ``jax.profiler.trace`` capture;
- :mod:`~fl4health_tpu.observability.telemetry` — IN-GRAPH round
  telemetry: a ``RoundTelemetry`` pytree of per-client training-health
  statistics compiled into the round programs themselves, so observability
  rides the chunked-scan fast path instead of forcing per-round dispatch;
- :mod:`~fl4health_tpu.observability.health` — the ``HealthWatchdog``
  consuming that telemetry against a declarative ``HealthPolicy``
  (NaN/Inf, loss divergence, dead clients, contribution skew), able to
  halt ``fit()`` with a structured ``TrainingHealthError``;
- :mod:`~fl4health_tpu.observability.introspect` — COMPILED-program
  introspection: per-program XLA cost/memory analysis (FLOPs, bytes
  accessed, HBM footprint), compile time and persistent-cache
  attribution, feeding measured MFU and the HBM-headroom gauge;
- :mod:`~fl4health_tpu.observability.exposition` /
  :mod:`~fl4health_tpu.observability.manifest` — a stdlib-only HTTP pull
  endpoint (``/metrics`` Prometheus text, ``/manifest`` run-provenance
  JSON) so a live ``fit()`` can be scraped mid-run;
- :mod:`~fl4health_tpu.observability.device_specs` — published per-chip
  peaks (bf16 FLOP/s, HBM capacity/bandwidth), the denominators for MFU
  and roofline positions.

:class:`Observability` is the facade ``FederatedSimulation`` accepts: it
wires all three to the process-wide defaults (so transport byte counters
land in the same snapshot) and owns export. Disabled, every hook is a
shared no-op — zero device syncs, zero allocations on the round hot path.
"""

from __future__ import annotations

import os
from typing import Any

from fl4health_tpu.observability.adminplane import AdminPlane, AdminRejection
from fl4health_tpu.observability.exposition import ScrapeServer
from fl4health_tpu.observability.fleet import FleetLedger
from fl4health_tpu.observability.slo import SLOEngine, SLOPolicy
from fl4health_tpu.observability.timeseries import RoundTimeSeries
from fl4health_tpu.observability.flightrec import (
    DEFAULT_WINDOW,
    FlightRecorder,
    SigtermShutdown,
    trap_sigterm,
)
from fl4health_tpu.observability.health import (
    HealthPolicy,
    HealthWatchdog,
    TrainingHealthError,
)
from fl4health_tpu.observability.introspect import (
    ProgramIntrospector,
    ProgramReport,
)
from fl4health_tpu.observability.manifest import config_hash, run_manifest
from fl4health_tpu.observability.jaxmon import (
    CompileMonitor,
    profile_round,
    synced,
)
from fl4health_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from fl4health_tpu.observability.spans import (
    _NULL_SPAN,
    Span,
    Tracer,
    get_tracer,
    set_tracer,
)
from fl4health_tpu.observability.tracectx import (
    TraceContext,
    flow_id,
    traced_handler,
)

__all__ = [
    "Observability",
    "AdminPlane",
    "AdminRejection",
    "SLOPolicy",
    "SLOEngine",
    "RoundTimeSeries",
    "FleetLedger",
    "TraceContext",
    "flow_id",
    "traced_handler",
    "FlightRecorder",
    "SigtermShutdown",
    "trap_sigterm",
    "Tracer",
    "Span",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "CompileMonitor",
    "HealthPolicy",
    "HealthWatchdog",
    "TrainingHealthError",
    "ProgramIntrospector",
    "ProgramReport",
    "ScrapeServer",
    "run_manifest",
    "config_hash",
    "get_tracer",
    "set_tracer",
    "get_registry",
    "set_registry",
    "profile_round",
    "synced",
]


class Observability:
    """One handle bundling tracer + registry + JAX hooks for a run.

    Defaults bind to the process-wide tracer/registry so free-function call
    sites (transport codec, coordinator) and the simulation share one
    snapshot; pass private instances for isolation (tests do).

    ``profile_round_idx`` selects ONE round for a ``jax.profiler.trace``
    capture under ``output_dir/xprof`` — device-level detail without paying
    profiler overhead on every round.

    ``telemetry`` (default on) compiles the in-graph
    :class:`~fl4health_tpu.observability.telemetry.RoundTelemetry` outputs
    into the round programs — per-client loss/grad-norm/update-norm
    statistics, non-finite counts, DP clip fraction and weight divergence —
    so a telemetry-on run keeps the chunked-scan fast path (the telemetry
    rides the existing fused transfers; loss trajectories stay
    bit-identical). ``watchdog`` attaches a
    :class:`~fl4health_tpu.observability.health.HealthWatchdog` that
    screens the telemetry each round and can halt ``fit()`` with a
    structured :class:`TrainingHealthError`.

    ``per_round_spans`` (opt-in) forces ``fit()`` onto the pipelined
    per-round path so the span timeline / device-time fences retain
    per-round granularity — with it off, enabling observability no longer
    demotes the chunked-scan execution mode (only ``profile_round_idx``
    still does).

    ``introspection`` (default on) captures each compiled round program's
    XLA cost/memory analysis at build time (``ProgramIntrospector``),
    which powers measured per-round MFU and the HBM-headroom gauge — all
    at program-build time, zero per-round cost. ``http_port`` (opt-in)
    starts the :class:`ScrapeServer` pull endpoint (``/metrics`` +
    ``/manifest``) for the handle's armed lifetime; ``http_port=0`` binds
    an OS-assigned port, readable from ``scrape_url``. The endpoint binds
    loopback by default — set ``http_host="0.0.0.0"`` for a remote
    Prometheus to reach it.

    The operations plane (both OFF by default): ``slo`` takes an
    :class:`~fl4health_tpu.observability.slo.SLOPolicy` and evaluates it
    each round in the epilogue (``fl_slo_*`` gauges, ``slo`` JSONL events,
    the ``degraded`` healthz state); ``admin_token`` arms the
    :class:`~fl4health_tpu.observability.adminplane.AdminPlane` behind
    ``POST /admin/scalars`` (shared-secret header auth) for live,
    journaled retunes of the hoisted scalars. Either one also arms the
    bounded :class:`~fl4health_tpu.observability.timeseries.RoundTimeSeries`
    (``ops_window`` rounds) that computes the serving KPIs.
    """

    def __init__(
        self,
        enabled: bool = True,
        output_dir: str | None = None,
        tracer: Tracer | None = None,
        registry: MetricsRegistry | None = None,
        profile_round_idx: int | None = None,
        sync_device: bool = True,
        telemetry: bool = True,
        per_round_spans: bool = False,
        watchdog: "HealthWatchdog | None" = None,
        introspection: bool = True,
        http_port: int | None = None,
        http_host: str = "127.0.0.1",
        flight_recorder: "bool | FlightRecorder" = True,
        flightrec_window: int | None = None,
        fleet_ledger: "bool | FleetLedger" = True,
        slo: "SLOPolicy | None" = None,
        admin_token: str | None = None,
        ops_window: int = 256,
    ):
        self.enabled = enabled
        self.output_dir = output_dir
        self.tracer = tracer if tracer is not None else get_tracer()
        self.registry = registry if registry is not None else get_registry()
        self.profile_round_idx = profile_round_idx
        self.sync_device = sync_device
        self.telemetry = telemetry
        self.per_round_spans = per_round_spans
        self.watchdog = watchdog
        self.introspection = introspection
        self.http_port = http_port
        self.http_host = http_host
        # Flight recorder (observability/flightrec.py): ALWAYS-ON by
        # default — a bounded ring of the last rounds' host-side records,
        # fed by data the round epilogues already pulled (zero device
        # syncs, recorder-on pinned bit-identical to recorder-off).
        # Bundles publish under output_dir on abnormal ends; without an
        # output_dir the ring stays queryable in memory.
        if isinstance(flight_recorder, FlightRecorder):
            self.flight_recorder: FlightRecorder | None = flight_recorder
        elif flight_recorder:
            self.flight_recorder = FlightRecorder(
                window=flightrec_window or DEFAULT_WINDOW
            )
        else:
            self.flight_recorder = None
        # Fleet ledger (observability/fleet.py): per-client LIFETIME
        # records at O(participated) host memory, same always-on/zero-sync
        # contract as the flight recorder. Rides the checkpoint frames via
        # the simulation (not here), and backs /fleet + /clients/<id>.
        if isinstance(fleet_ledger, FleetLedger):
            self.fleet_ledger: FleetLedger | None = fleet_ledger
        elif fleet_ledger:
            self.fleet_ledger = FleetLedger()
        else:
            self.fleet_ledger = None
        # Operations plane (PR 19): OFF unless an SLOPolicy or admin token
        # arms it. Host-side only — fed from epilogue summaries the run
        # already pulled, so arming it cannot add a device sync, and the
        # off path is bit-identical by construction.
        self.slo: "SLOEngine | None" = (
            SLOEngine(slo, self.registry) if slo is not None else None
        )
        self.admin: "AdminPlane | None" = (
            AdminPlane(admin_token, self.registry)
            if admin_token is not None else None
        )
        self.timeseries: "RoundTimeSeries | None" = (
            RoundTimeSeries(window=ops_window)
            if (self.slo is not None or self.admin is not None) else None
        )
        self._unhealthy: str | None = None
        self._degraded: str | None = None
        self.introspector = ProgramIntrospector(self.registry)
        self._manifest: dict[str, Any] = {}
        self._scrape_server: ScrapeServer | None = None
        self.compile_monitor = CompileMonitor(self.registry)
        # Ownership of the tracer's enabled flag: only the handle that
        # actually flipped it on may flip it off (and clear its events) at
        # shutdown — a disabled Observability, or one handed an
        # already-enabled tracer, must not reset state it doesn't own.
        self._owns_tracer_enable = False
        if enabled:
            self.start()

    @property
    def telemetry_enabled(self) -> bool:
        """True when the round programs should compile in-graph
        RoundTelemetry outputs."""
        return self.enabled and self.telemetry

    @property
    def introspection_enabled(self) -> bool:
        """True when compiled-program introspection should run at program
        build time."""
        return self.enabled and self.introspection

    @property
    def scrape_url(self) -> str | None:
        """Base URL of the live scrape endpoint, or None when not serving."""
        return self._scrape_server.url if self._scrape_server else None

    # -- run manifest ----------------------------------------------------
    def update_manifest(self, fields: "dict[str, Any]") -> dict:
        """Merge ``fields`` into the run manifest served at ``/manifest``
        (and exported as manifest.json). Returns the current manifest."""
        self._manifest.update(fields)
        return dict(self._manifest)

    @property
    def manifest(self) -> dict:
        return dict(self._manifest)

    def start(self) -> "Observability":
        """(Re-)arm the hooks: enable the tracer, install the compile
        monitor, reset the watchdog's per-run state. Called by ``__init__``
        and again by ``FederatedSimulation`` at each ``fit()`` so a handle
        survives multiple runs (``shutdown`` disarms it between them).
        Idempotent; no-op when disabled."""
        if self.enabled:
            self._unhealthy = None  # per-run: a fresh fit() is healthy
            self._degraded = None
            if self.watchdog is not None:
                self.watchdog.reset()
            if not self.tracer.enabled:
                # flipping the (possibly process-global) tracer on is what
                # makes transport/engine spans visible
                self.tracer.enabled = True
                self._owns_tracer_enable = True
            if self.output_dir is not None:
                # crash-safe black box: mirror spans to trace.json AS THEY
                # HAPPEN (Chrome JSON Array Format stays loadable even if
                # the process dies mid-run; export() finalizes the
                # complete envelope over it at shutdown)
                os.makedirs(self.output_dir, exist_ok=True)
                self.tracer.stream_to(
                    os.path.join(self.output_dir, "trace.json")
                )
            self.compile_monitor.install()
            if self.http_port is not None and self._scrape_server is None:
                # live pull endpoint for the armed lifetime of the handle —
                # a scrape reads host-side floats only (no device work)
                ledger = self.fleet_ledger
                self._scrape_server = ScrapeServer(
                    self.registry,
                    manifest_provider=lambda: dict(self._manifest),
                    host=self.http_host,
                    port=self.http_port,
                    health_provider=lambda: self._unhealthy,
                    fleet_provider=(
                        (lambda: ledger.summary()) if ledger is not None
                        else None
                    ),
                    client_provider=(
                        (lambda cid: ledger.get(cid)) if ledger is not None
                        else None
                    ),
                    degraded_provider=lambda: self._degraded,
                    slo_provider=(
                        (lambda: self.slo.standing())
                        if self.slo is not None else None
                    ),
                    admin_plane=self.admin,
                )
        return self

    # -- abnormal-end surface -------------------------------------------
    @property
    def unhealthy_reason(self) -> str | None:
        """The verdict summary once the run halted, else None (healthy)."""
        return self._unhealthy

    def mark_unhealthy(self, reason: str) -> None:
        """Flip ``/healthz`` to 503 with ``reason`` as the body — called on
        a watchdog halt and on every postmortem bundle dump, so the armed
        scrape endpoint stops reporting a dying run healthy."""
        self._unhealthy = str(reason)

    def mark_healthy(self) -> None:
        """Reset the ``/healthz`` verdict back to 200 ("ok") — the inverse
        of :meth:`mark_unhealthy`. The recovery supervisor calls this once
        a self-healed run's probation window passes, so an orchestrator
        polling the armed scrape endpoint sees the recovery instead of a
        503 that stays sticky until the next ``start()``."""
        self._unhealthy = None

    @property
    def degraded_slo(self) -> str | None:
        """Name of the SLO objective standing in breach, else None."""
        return self._degraded

    def mark_degraded(self, slo_name: str) -> None:
        """Flip ``/healthz`` to 200 ``degraded: <slo>`` — the limping state
        between healthy and the 503 a halt raises. Dead beats limping:
        a 503 verdict always wins over this channel."""
        self._degraded = str(slo_name)

    def clear_degraded(self) -> None:
        self._degraded = None

    def dump_bundle(self, verdict: "dict[str, Any]") -> str | None:
        """Publish a postmortem bundle (``observability/bundle.py``) under
        ``output_dir`` from the flight recorder's ring + the live trace/
        registry/manifest. Returns the bundle path, or None when disabled
        or there is nowhere to publish. Marks the run unhealthy."""
        if not self.enabled or self.output_dir is None:
            return None
        from fl4health_tpu.observability.bundle import dump_bundle

        path = dump_bundle(
            self.output_dir, verdict,
            recorder=self.flight_recorder,
            tracer=self.tracer if self.tracer.enabled else None,
            registry=self.registry,
            manifest=self._manifest or None,
            fleet=(self.fleet_ledger.snapshot()
                   if self.fleet_ledger is not None else None),
        )
        self.mark_unhealthy(
            f"{verdict.get('kind', 'exception')}: "
            f"{verdict.get('message', '')} (bundle: {path})"
        )
        self.registry.counter(
            "fl_flightrec_bundles_total",
            help="postmortem bundles published on abnormal ends",
        ).inc()
        return path

    # -- tracing ---------------------------------------------------------
    def span(self, name: str, cat: str = "round", **args: Any):
        if not self.enabled:
            return _NULL_SPAN
        return self.tracer.span(name, cat=cat, **args)

    def instant(self, name: str, **args: Any) -> None:
        if self.enabled:
            self.tracer.instant(name, **args)

    # -- metrics ---------------------------------------------------------
    def counter(self, name: str, help: str = "", labels=None) -> Counter:
        return self.registry.counter(name, help=help, labels=labels)

    def gauge(self, name: str, help: str = "", labels=None) -> Gauge:
        return self.registry.gauge(name, help=help, labels=labels)

    def histogram(self, name: str, help: str = "", labels=None, **kw) -> Histogram:
        return self.registry.histogram(name, help=help, labels=labels, **kw)

    def log_event(self, event: str, **fields: Any) -> dict | None:
        if not self.enabled:
            return None
        rec = self.registry.log_event(event, **fields)
        if event == "recovery" and self.timeseries is not None:
            # the supervisor's self-heal ladder routes through here — fold
            # engage/probation_passed/halt into the MTTR KPI
            self.timeseries.note_recovery(fields.get("phase"),
                                          ts=rec.get("ts"))
        return rec

    def snapshot(self) -> dict:
        return self.registry.snapshot()

    # -- operations plane ------------------------------------------------
    def observe_round_kpis(self, rnd: int, summary: "dict[str, Any]", *,
                           fit_loss: float | None = None,
                           eval_loss: float | None = None):
        """Feed one epilogue round summary to the ops plane: refresh the
        KPI time-series, evaluate the SLO policy, and drive the degraded
        healthz channel. No-op (returns None) when the plane is unarmed —
        the default path stays byte-for-byte untouched."""
        ts = self.timeseries
        if not self.enabled or ts is None:
            return None
        kpis = ts.observe_round(summary, fit_loss=fit_loss,
                                eval_loss=eval_loss)
        if self.slo is None:
            return kpis
        verdict = self.slo.evaluate(rnd, kpis)
        if verdict["degraded_slo"] is not None:
            self.mark_degraded(verdict["degraded_slo"])
        else:
            self.clear_degraded()
        return verdict

    # -- JAX hooks -------------------------------------------------------
    def fence(self, tree: Any) -> tuple[Any, float]:
        """``block_until_ready`` fence returning (tree, wait_seconds); a pure
        pass-through when disabled — no new syncs on the disabled path. A
        fence that does block is a ``device_fence`` span: the part of a
        round its producer thread spends stopped, waiting for the device."""
        if not (self.enabled and self.sync_device):
            return tree, 0.0
        with self.tracer.span("device_fence"):
            return synced(tree)

    def maybe_profile(self, round_idx: int):
        """``jax.profiler.trace`` context for the chosen round, else no-op."""
        if (
            self.enabled
            and self.profile_round_idx is not None
            and round_idx == self.profile_round_idx
            and self.output_dir is not None
        ):
            return profile_round(os.path.join(self.output_dir, "xprof"))
        return profile_round(None)

    # -- export ----------------------------------------------------------
    def export(self) -> dict[str, str]:
        """Write trace.json (Chrome trace events), metrics.prom (Prometheus
        text), metrics.jsonl (event log) under ``output_dir``. Returns
        {artifact: path}; empty when disabled or no output_dir."""
        if not self.enabled or self.output_dir is None:
            return {}
        os.makedirs(self.output_dir, exist_ok=True)
        paths = {
            "trace": self.tracer.export(os.path.join(self.output_dir, "trace.json")),
            "prometheus": self.registry.export_prometheus(
                os.path.join(self.output_dir, "metrics.prom")
            ),
            "events": self.registry.dump_jsonl(
                os.path.join(self.output_dir, "metrics.jsonl")
            ),
        }
        if self._manifest:
            import json

            from fl4health_tpu.core.io import atomic_write

            mpath = os.path.join(self.output_dir, "manifest.json")
            with atomic_write(mpath) as f:
                f.write(json.dumps(self._manifest, indent=2, default=str))
            paths["manifest"] = mpath
        return paths

    def shutdown(self) -> dict[str, str]:
        """Export artifacts and disarm every hook: detach the compile
        monitor (so a later run's monitor doesn't double-count compile
        events through the global fan-out), and — if this handle is the one
        that enabled the tracer — disable it and drop its exported events
        (a long-lived process must not accumulate spans forever, nor re-export
        run 1's events into run 2's trace). ``start()`` re-arms."""
        paths = self.export()
        self.compile_monitor.uninstall()
        if self._scrape_server is not None:
            self._scrape_server.close()
            self._scrape_server = None
        if self._owns_tracer_enable:
            self.tracer.enabled = False
            # a stream export() didn't finalize (no output_dir, or a
            # different path) still terminates cleanly here
            self.tracer.close_stream()
            self.tracer.clear()
            self._owns_tracer_enable = False
        if "events" in paths:
            # only after a successful JSONL dump — with no output_dir the
            # events stay readable programmatically (registry.events)
            self.registry.clear_events()
        return paths
