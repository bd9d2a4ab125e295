"""Async round pipeline — overlap host work with device execution.

Round-5 VERDICT measured ~1.5 s of host Python per round against ~0.1 s of
device busy time: the TPU sat idle while the driver loop did failure
screening, checkpointing, record construction and reporter I/O between
dispatches. FedJAX (arXiv:2108.02117) wins FL-simulation throughput by
keeping the accelerator saturated across the round loop; these two helpers
are the host half of that design for ``FederatedSimulation.fit``:

- :class:`RoundConsumer` — a bounded single-worker queue that executes each
  round's host-side epilogue (failure policy, checkpoint decisions,
  ``RoundRecord`` construction, reporter fan-out, in-graph telemetry
  recording + the ``HealthWatchdog`` screen) in a background thread
  while the device already runs the next round. FIFO ordering is guaranteed
  (one worker), ``flush()`` is a completion barrier, and the first exception
  raised by round *r*'s epilogue (e.g. ``ClientFailuresError`` or the
  watchdog's ``TrainingHealthError``) is re-raised into the producer at the
  next ``submit``/``flush``. The round's ``RoundTelemetry`` pytree rides the
  consumer's single fused device->host transfer — enabling telemetry adds
  zero producer-side syncs.

- :class:`RoundPrefetcher` — builds round *r+1*'s host-side index plan
  (pure numpy) and stages its gathered batches on device while round *r*
  executes. If ``set_train_data`` swapped the data stacks after staging
  (a ``train_data_provider`` refresh), the staged gather is discarded and
  re-issued against the fresh stacks — the *plan* (index math) is still
  reused, so only the cheap device gather is re-paid.

Neither helper touches device buffers that donation could invalidate: the
consumer receives *result* arrays (fresh outputs, never donated back into a
later round) or device-side snapshot copies; the prefetcher reads only the
immutable per-round plan inputs and the data stacks it re-validates by
identity.

Buffered-async runs (``server/async_schedule.py``) reuse both helpers with
shifted indices: buffer-fill event *e* restarts its consumed clients on
data plan ``e+1``, so the async producer schedules/takes plan index
``e+1`` while event *e* executes (the prologue takes plan 1). The plan
index IS the prefetcher's contract — it never assumes indices are round
numbers, only that ``take(i)`` follows ``schedule(i)`` — which is what
lets one prefetcher serve both cadences.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any

from fl4health_tpu.core.workqueue import SingleWorkerQueue


class RoundConsumer(SingleWorkerQueue):
    """Single-worker FIFO executor for per-round host epilogues.

    ``maxsize`` bounds how many rounds of host work may be pending — the
    producer blocks on ``submit`` once the device is that far ahead, so host
    memory (result trees, checkpoint snapshots) stays bounded. Queue,
    ordering, flush-barrier and exception contracts come from
    :class:`~fl4health_tpu.core.workqueue.SingleWorkerQueue`.
    """

    def __init__(self, maxsize: int = 2, name: str = "fl-round-consumer"):
        super().__init__(maxsize=maxsize, name=name)
        # newest round whose epilogue FINISHED (not merely was submitted) —
        # the flight recorder's verdict quotes this so a postmortem can
        # distinguish "round r recorded" from "round r+1 died in flight"
        self.last_completed_round: int | None = None

    def submit_round(self, round_idx: int, job) -> None:
        """Submit one round's host epilogue, tracking its completion in
        ``last_completed_round`` once the job ran (worker thread, FIFO —
        the value is monotone)."""

        def _job():
            job()
            self.last_completed_round = int(round_idx)

        self.submit(_job)


class RoundPrefetcher:
    """Stage round *r+1*'s batches while round *r* executes.

    ``schedule(r)`` computes the host index plan (numpy) and dispatches the
    device gather in a worker thread; ``take(r)`` returns the staged batches,
    falling back to synchronous construction on a miss. Staleness rule: if
    the simulation's train stacks were swapped (``set_train_data``) between
    staging and ``take``, the plan is re-gathered against the fresh stacks —
    correctness over reuse.

    Under a device mesh the staged batch stack is ``device_put`` onto the
    builder's clients-axis sharding as part of staging — the clients-axis
    split of round *r+1*'s data overlaps round *r*'s execution instead of
    riding the dispatch as an implicit reshard. Without a mesh, staging is
    exactly the pre-mesh behavior.
    """

    def __init__(self, sim: Any):
        self._sim = sim
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="fl-round-prefetch"
        )
        self._pending: tuple[int, Future] | None = None

    def _place(self, batches):
        # one placement rule everywhere: the builder's put + the builder's
        # own clients sharding (no-op when unsharded), so staging policy
        # can't drift from the other device_put sites.
        #
        # Thread-safety note: this device_put runs on the worker thread
        # while the main thread dispatches the current round's program.
        # That is safe where eager multi-device COMPUTATIONS are not —
        # an eager sharded gather here deadlocks against the concurrent
        # dispatch (rendezvous-synchronized executable launches from two
        # threads; see the train-bank comment in simulation.__init__) —
        # because device_put issues independent per-device transfers, not
        # a collective program. Pinned green on the 8-device virtual mesh
        # that reproduces the gather deadlock; if a real multi-chip
        # backend ever hangs here, fall back to placing in take() on the
        # caller's thread at the cost of the staging overlap.
        builder = self._sim._program_builder
        return builder.put(batches, builder.client_sharding())

    def schedule(self, round_idx: int) -> None:
        sim = self._sim
        if getattr(sim, "_cohort_active", False):
            # cohort-slot staging: sample the round's cohort ids, gather
            # its [K, ...] slot tensors from the host registry and
            # device_put them (sharded under a mesh) — all of it a pure
            # function of (rng, round, registry data), so it runs here
            # while the previous round executes. Per-client STATE is
            # deliberately absent (it depends on the previous round's
            # registry scatter — the producer gathers it after its gate).
            self._pending = (
                round_idx,
                self._pool.submit(sim._stage_cohort_round, round_idx),
            )
            return
        # capture the stacks NOW: take() compares by identity to detect a
        # mid-flight set_train_data swap
        x_stack, y_stack = sim._x_train_stack, sim._y_train_stack

        def build():
            from fl4health_tpu.clients import engine

            plan = sim._round_plan(round_idx)
            batches = self._place(
                engine.gather_batches(x_stack, y_stack, *plan)
            )
            return (x_stack, y_stack), plan, batches

        self._pending = (round_idx, self._pool.submit(build))

    def schedule_chunk(self, start_round: int, k: int) -> None:
        """Cohort chunked route: stage chunk ``[start_round,
        start_round+k)``'s sampled draws, stacked slot tensors and window
        ids on the worker thread while the previous chunk's device work
        runs — the double-buffered half of the in-graph window exchange.
        Window STATE rows are deliberately absent: they have a
        read-after-write dependency on the previous chunk's registry
        scatter, so the driver gathers them on its own thread after it."""
        sim = self._sim
        self._pending = (
            ("chunk", start_round),
            self._pool.submit(sim._stage_cohort_chunk, start_round, k),
        )

    def take_chunk(self, start_round: int, k: int):
        """Staged chunk tensors from :meth:`schedule_chunk`; synchronous
        staging on a miss (first chunk, or a resume realigned the
        boundaries)."""
        sim = self._sim
        pending, self._pending = self._pending, None
        if pending is not None and pending[0] == ("chunk", start_round):
            return pending[1].result()
        return sim._stage_cohort_chunk(start_round, k)

    def take(self, round_idx: int):
        """Round ``round_idx``'s batches. The ``prefetch_wait`` span is the
        time the round waited for them: the rest of the worker's staging on
        a hit, the whole synchronous construction on a miss."""
        sim = self._sim
        pending, self._pending = self._pending, None
        hit = pending is not None and pending[0] == round_idx
        with sim.observability.span("prefetch_wait", round=round_idx,
                                    hit=hit):
            if getattr(sim, "_cohort_active", False):
                if hit:
                    return pending[1].result()
                return sim._stage_cohort_round(round_idx)
            if not hit:
                return self._place(sim._round_batches(round_idx))
            (x_stack, y_stack), plan, batches = pending[1].result()
            if x_stack is sim._x_train_stack and y_stack is sim._y_train_stack:
                return batches
            # data refreshed after staging: same plan, fresh gather
            from fl4health_tpu.clients import engine

            return self._place(engine.gather_batches(
                sim._x_train_stack, sim._y_train_stack, *plan
            ))

    def close(self) -> None:
        self._pending = None
        self._pool.shutdown(wait=False, cancel_futures=True)
