"""The scheduler control loop.

Behavioral re-derivation of manager/scheduler/scheduler.go: an event loop over
store watches that moves PENDING tasks to ASSIGNED. Differences from the
reference are architectural, per SURVEY.md §7:

  * ticks are *batched*: all dirty groups are encoded into dense arrays and
    placed by one backend call — the greedy CPU engine for small ticks, the
    JAX water-fill kernel above `JAX_THRESHOLD` task×node products
    (backend="auto"), instead of per-task Go heap walks;
  * placement is canonically deterministic (spread.py) rather than
    Go-map-iteration dependent.

Matching reference behaviors: 50 ms commit debounce with 1 s cap
(scheduler.go:149-155), preassigned (global-service) tasks validated against
the filter pipeline without spread scoring (:398-426), in-transaction
re-validation of node state when committing decisions (:533-604), failed
decisions returned to the unassigned pool, and pipeline explanations written
to task status on failure (:923-968).
"""
from __future__ import annotations

import logging
import threading
import time
from collections import defaultdict, deque

import numpy as np

from ..api.objects import (
    EventCommit,
    EventCreate,
    EventDelete,
    EventUpdate,
    Node,
    Task,
)
from ..api.types import NodeStatusState, TaskState
from ..store import by
from ..store.memory import (
    ASSIGN_NODE_NOT_READY,
    ASSIGN_OK,
    MAX_CHANGES_PER_TRANSACTION,
    MemoryStore,
)
from ..store.watch import ChannelClosed
from ..utils import failpoints, lifecycle, trace
from .batch import apply_placements, cpu_schedule_encoded, materialize_orders
from .encode import IncrementalEncoder, TaskGroup
from .filters import Pipeline
from .nodeinfo import NodeInfo

log = logging.getLogger("swarmkit_tpu.scheduler")

COMMIT_DEBOUNCE = 0.05   # reference: 50ms
MAX_LATENCY = 1.0        # reference: 1s
# task×node products above which the TPU kernel wins. Two regimes:
# blocking ticks pay the full counts round-trip (~0.1s fixed through the
# old remote dev link), so the bar is high; pipelined ticks hide the pull
# under the commit/debounce window and their bar is the HOST-side floor
# only — encode + dispatch, measured 1.5-3 ms/tick after the round-4
# group-table device cache (was ~6 ms), crossing CPU fill at ~100-250k
# products on that link. Not re-measured on an attached chip (ROADMAP
# Queue 3 item 5).
JAX_THRESHOLD = 200_000
PIPELINED_JAX_THRESHOLD = 100_000
# raft-backed batched write-back: sub-transactions (≤ MAX_CHANGES each)
# pipelined through propose_async share the group-commit plane's WAL
# fsync + replication flush (store.batch pipeline_depth semantics)
WRITEBACK_PIPELINE_DEPTH = 16
# cold-start policy (backend="auto"): with NO device-resident state yet,
# a jax tick pays a full upload plus a BLOCKING counts round trip
# (~0.1 s fixed through the old remote link) while the CPU fill at small
# node counts costs less than that RTT — the fill is node-bound, so N
# is the predictor. First wave goes to the CPU oracle below this node
# count; the device state warms on the next wave's dispatch instead.
COLD_CPU_NODES = 8_192


class Scheduler:
    def __init__(self, store: MemoryStore, backend: str = "auto",
                 jax_threshold: int | None = None, pipeline: bool = False,
                 mesh=None, async_commit: bool = False,
                 columnar_writeback: bool = True,
                 strategy: str = "spread", topology: str | None = None):
        """backend: "auto" picks per tick by task×node product against
        `jax_threshold` (default JAX_THRESHOLD); "cpu"/"jax" pin the path;
        "mesh" pins the jax path AND shards the device-resident node state
        over every visible device's `nodes` mesh axis (parallel/mesh.py
        layout — the production multi-chip mode). `mesh` narrows it: an
        int takes the first n devices, a jax.sharding.Mesh is used as-is.
        The right threshold is deployment-specific — an attached chip
        should amortize far sooner than the old remote dev link the
        default was tuned on — so swarmd exposes both knobs
        (--scheduler-backend / --jax-threshold, SURVEY §7).

        pipeline=True enables sustained-load tick pipelining on the jax
        path (ops/pipeline.py reorder): a tick dispatches its fill and
        returns; the NEXT tick pulls the counts — which rode the link in
        the background through the debounce window — commits them, and
        dispatches again, with the commit overlapping the new transfer.
        Placement latency gains one debounce period; steady throughput
        stops paying the blocking device pull. Commit conflicts (tasks
        raced/deleted, nodes gone) abandon the optimistic fold: the
        resident carry invalidates and fingerprint deltas re-encode the
        touched rows — the same self-healing the serial path uses.

        async_commit=True (pipelined jax path only) moves the commit's
        heavy half — slot materialization, the add_task walk, the store
        transaction, the fingerprint restamp — onto one background
        CommitWorker (ops/commit.py), overlapping it with the next
        wave's device dispatch and D2H pull. Every reader of scheduler
        host state (the event handler, the serial tick path, stop)
        takes a worker barrier first; a worker exception re-raises into
        the next tick, whose existing failure handler owns the heal.

        strategy selects the scoring engine for EVERY group (ISSUE 19):
        "spread" (default water-fill), "binpack" (fullest feasible node
        first, flat — spread preferences ignored), or "topology"
        (spread with `topology` — a node.labels.*/engine.labels.*
        descriptor — prepended as the outermost balance axis of every
        group). Both new strategies keep the kernel↔CPU-oracle
        bit-parity bar (scheduler/spread.py binpack_reference /
        topology_fill are the oracles)."""
        self.store = store
        self.backend = backend
        self.mesh = mesh
        # wave write-back through the columnar store plane (ISSUE 11):
        # one store.assign_wave per wave — vectorized in-tx validation
        # against the column mirror, shallow patches instead of tree
        # copies. Auto-off when the store runs without the mirror
        # (SWARMKIT_TPU_NO_COLUMNAR) — the object path is the fallback.
        self.columnar_writeback = bool(
            columnar_writeback and getattr(store, "columnar", None)
            is not None)
        self.jax_threshold = (
            (PIPELINED_JAX_THRESHOLD if pipeline else JAX_THRESHOLD)
            if jax_threshold is None else jax_threshold)
        self.pipeline = pipeline
        if async_commit and pipeline:
            from ..ops.commit import CommitWorker

            self._commit_worker = CommitWorker(name="sched-commit")
        else:
            if async_commit:
                # the commit plane only exists on the pipelined path —
                # dropping the flag silently would let an operator
                # believe async commit engaged when it never could
                log.warning("scheduler: async_commit requires "
                            "pipeline=True (--scheduler-pipeline); "
                            "running synchronous commits")
            self._commit_worker = None
        # set by the worker when an async commit came back unclean:
        # (problem, counts) awaiting the main-thread heal at the next
        # barrier (force_numeric_reencode + resident invalidate +
        # discard of any dispatch primed on the lying fold)
        self._worker_unclean = None
        # conflicted decisions in the LAST commit (in-tx re-validation
        # rejected a placement: node no longer READY / volume choose
        # failed). Conflicts rely on "node/task events retrigger the
        # tick" — but a wave committed BEHIND the async plane may
        # conflict on an event the run loop consumed while the wave was
        # in flight, so the completing tick must retry the pool itself
        # (see _tick_pipelined's gate bypass)
        self._last_commit_conflicts = 0
        # task-id sets of waves whose heavy commit may still ride the
        # plane (appended at submit, removed by the job's tail). On the
        # overlap path the next wave's prime excludes them: their
        # unassigned-pool pops happen on the worker thread, so without
        # the exclusion a still-uncommitted task could be re-grouped
        # into a new wave (double placement). Cleared at every barrier.
        self._pending_commit_ids: deque = deque()
        # observability: completed waves whose heavy commit was submitted
        # BEFORE the next prime (the encode/commit overlap path)
        self.overlapped_commits = 0
        # (problem, PendingCounts, frozenset of in-flight task ids)
        self._inflight = None
        self.node_infos: dict[str, NodeInfo] = {}
        self.unassigned: dict[str, Task] = {}
        self.preassigned: dict[str, Task] = {}
        self.pending_spec_version: dict[str, int] = {}
        from ..csi.volumes import VolumeSet
        self.volume_set = VolumeSet()
        # persistent dictionary encoder: node rows and vocabs survive across
        # ticks; only fingerprint-dirty nodes re-encode (verdict #6).
        # tracked=True (round 6): the scheduler feeds the dirty set
        # explicitly (every NodeInfo mutation site below marks), so a
        # steady tick's encode skips the O(N) fingerprint scan entirely
        # and nodes_clean degrades to a flag check — the zero-scan fast
        # path AND the encode/commit overlap's gate.
        self.encoder = IncrementalEncoder(tracked=True, strategy=strategy,
                                          topology=topology)
        # device-resident node tables (ops.resident): created on first jax
        # tick; deltas ride the encoder's dirty-row bookkeeping
        self._resident = None
        # cold-start policy bookkeeping: True after the one CPU wave a
        # cold period gets at small N; reset whenever a jax tick runs
        self._cold_cpu_done = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.ticks = 0
        # ticks that raised in run() and were retried: a device fault
        # (compile error, deleted buffer) surfaces here, not as a hang
        self.tick_failures = 0

    # ------------------------------------------------------------- lifecycle
    def start(self):
        self._thread = threading.Thread(target=self.run, daemon=True,
                                        name="scheduler")
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
        if self._commit_worker is not None:
            self._commit_worker.close()

    # ------------------------------------------------------ async commit plane
    def _drain_commit_plane(self, swallow: bool = False):
        """Barrier on the async heavy commit before any read/mutation of
        scheduler host state (node_infos, encoder fingerprints, the
        unassigned pool, volume_set), then run the pending unclean heal.
        swallow=True (event-handler path): a worker exception must not
        crash the run loop here — the worker stays poisoned and the next
        tick's barrier re-raises it into the guarded tick path."""
        w = self._commit_worker
        if w is None:
            return
        try:
            w.barrier()
        except Exception:
            if not swallow:
                raise
        else:
            # every submitted heavy retired cleanly: nothing can still
            # pop the unassigned pool, so the prime-time exclusion sets
            # are stale (poisoned-and-dropped jobs never ran their
            # removal tail — without this clear their tasks would stay
            # excluded forever)
            self._pending_commit_ids.clear()
        if self._worker_unclean is not None:
            self._heal_unclean()

    def _heal_unclean(self):
        """Main-thread half of the async unclean-commit heal (same
        semantics as the sync path's inline heal): poison the placed-on
        rows so the next encode re-derives them from the NodeInfo
        objects, resync the device, and discard any dispatch primed on
        the bad fold."""
        problem, counts = self._worker_unclean
        self._worker_unclean = None
        self.encoder.force_numeric_reencode(
            np.flatnonzero(counts.sum(axis=0)))
        if self._resident is not None:
            self._resident.invalidate()
        if self._inflight is not None:
            _p2, h2, _ids2 = self._inflight
            self._inflight = None
            try:
                h2.get()
            except Exception:
                # the dispatch is being DISCARDED and the resident carry
                # was just invalidated — a device error pulling a
                # wave we won't use must not escape (this heal also runs
                # on the event-drain path, which has no retry handler)
                log.warning("discarding in-flight wave: counts pull "
                            "failed", exc_info=True)

    def _submit_heavy(self, problem, counts, ids: frozenset):
        """Enqueue one wave's heavy commit on the plane, bracketed by the
        prime-time exclusion bookkeeping: `ids` stays in
        `_pending_commit_ids` until the job's tail runs (worker thread;
        deque append/remove are GIL-atomic), so an overlapped prime can
        never re-group a task whose pool pop is still in flight. Dropped
        (poisoned) jobs skip the tail; the barrier paths clear the
        leftovers. The job joins the submitting tick's trace
        (trace.wrap: identity when disarmed)."""
        self._pending_commit_ids.append(ids)

        def job():
            try:
                self._commit_heavy(problem, counts)
            finally:
                try:
                    self._pending_commit_ids.remove(ids)
                except ValueError:
                    pass    # a barrier path already cleared it

        self._commit_worker.submit(trace.wrap("tick.commit_heavy", job))

    def _commit_heavy(self, problem, counts):
        """The commit's heavy half, run on the CommitWorker: slot
        materialization, store write-back with in-tx re-validation, the
        wave-bulk add_task walk, and the fingerprint restamp. An unclean
        outcome is recorded for the next barrier's main-thread heal.

        Failpoints bracket every stage boundary (`commit.materialize`,
        `commit.writeback` before the store transaction + walk — the
        walk itself has `commit.walk` in batch.apply_placements — and
        `commit.restamp`): a crash at ANY of them must poison the plane
        and heal at the next barrier, not just the boundaries production
        incidents happen to hit."""
        try:
            failpoints.fp("commit.materialize")
            with trace.span("tick.commit.materialize"):
                orders = materialize_orders(problem, counts)
            failpoints.fp("commit.writeback")
            with trace.span("tick.commit.writeback"):
                clean = self._apply_decisions(problem, orders, counts,
                                              deferred_fold=True)
        except BaseException:
            # a CRASH in the heavy half is an unclean commit too: the
            # optimistic fold already ran on the tick thread, but the
            # add_task walk (the thing that bumps mutation counters) may
            # not have — without recording the wave, the barrier heal
            # would invalidate the device yet leave the encoder's folded
            # rows as phantom reservations no fingerprint ever clears
            # (found by the seeded chaos harness, CHAOS_SEED=0)
            self._worker_unclean = (problem, counts)
            raise
        if clean:
            failpoints.fp("commit.restamp")
            self.encoder.restamp_counts(problem, counts)
        else:
            self._worker_unclean = (problem, counts)

    # ------------------------------------------------------------------ init
    def _setup(self):
        """Snapshot + subscribe (reference setupTasksList, scheduler.go:68-125)."""

        def snap(tx):
            return tx.find_tasks(), tx.find_nodes(), tx.find_volumes()

        # Unbounded subscription: the scheduler is a trusted in-process
        # consumer and must never be shed as a slow subscriber — a closed
        # channel would silently stop all scheduling.
        (tasks, nodes, volumes), ch = self.store.view_and_watch(snap, limit=None)
        for v in volumes:
            self.volume_set.add_or_update_volume(v)
        tasks_by_node: dict[str, dict[str, Task]] = defaultdict(dict)
        for t in tasks:
            if t.volumes and t.desired_state <= TaskState.RUNNING:
                self.volume_set.reserve_task(t)
            if t.status.state < TaskState.PENDING or t.status.state > TaskState.RUNNING:
                continue
            # desired_state == COMPLETE covers job-mode tasks; anything past
            # that (SHUTDOWN/REMOVE/...) is being torn down and must not be
            # scheduled (reference scheduler.go:96-99)
            if t.desired_state > TaskState.COMPLETE:
                continue
            if t.status.state == TaskState.PENDING and not t.node_id:
                self.unassigned[t.id] = t
            elif t.status.state == TaskState.PENDING and t.node_id:
                self.preassigned[t.id] = t
            if t.node_id and t.status.state >= TaskState.ASSIGNED:
                tasks_by_node[t.node_id][t.id] = t
        for n in nodes:
            self._add_or_update_node(n, tasks_by_node.get(n.id, {}))
        return ch

    # ----------------------------------------------------------------- nodes
    def _add_or_update_node(self, node: Node, tasks: dict[str, Task] | None = None):
        existing = self.node_infos.get(node.id)
        if tasks is None:
            tasks = existing.tasks if existing else {}
        desc = node.description
        total = desc.resources.copy() if desc else None
        from ..api.specs import Resources
        avail = total if total is not None else Resources()
        info = NodeInfo.new(node, dict(tasks), avail)
        if existing:
            info.recent_failures = existing.recent_failures
        self.node_infos[node.id] = info
        # tracked-encoder dirty feed: a replaced object re-encodes its
        # row's string columns; a NEW node changes the row set
        if existing is not None:
            self.encoder.mark_replaced(info)
        else:
            self.encoder.mark_node_set_changed()

    def _remove_node(self, node_id: str):
        if self.node_infos.pop(node_id, None) is not None:
            self.encoder.mark_node_set_changed()

    # ---------------------------------------------------------------- events
    def _handle(self, ev) -> bool:
        """Returns True when the event makes a tick necessary."""
        # event handling mutates node_infos / volume_set / the pools —
        # the async heavy commit must be fully retired first
        self._drain_commit_plane(swallow=True)
        if isinstance(ev, (EventCreate, EventUpdate)) and isinstance(ev.obj, Task):
            t = ev.obj
            if (t.status.state == TaskState.PENDING
                    and t.desired_state <= TaskState.COMPLETE):
                if t.node_id:
                    self.preassigned[t.id] = t
                else:
                    self.unassigned[t.id] = t
                return True
            # track running tasks on nodes for accurate counts
            if t.node_id and t.node_id in self.node_infos:
                info = self.node_infos[t.node_id]
                if t.status.state > TaskState.RUNNING:
                    # only an *observed* terminal state releases resources;
                    # a desired-state change alone still has the container
                    # running (reference scheduler.go:294 deletes on observed
                    # state, desired crossings only flip active counts via
                    # add_task, nodeinfo.go:111-119)
                    if info.remove_task(t):
                        self.encoder.mark_numeric(info)
                        if t.volumes:
                            self.volume_set.release_task(t)
                        if t.status.state == TaskState.FAILED:
                            key = (t.service_id,
                                   t.spec_version.index if t.spec_version else 0)
                            info.task_failed(key)
                        return True
                else:
                    if info.add_task(t):
                        self.encoder.mark_numeric(info)
            if (t.status.state > TaskState.PENDING
                    or t.desired_state > TaskState.COMPLETE):
                self.unassigned.pop(t.id, None)
                self.preassigned.pop(t.id, None)
            return False
        if isinstance(ev, EventDelete) and isinstance(ev.obj, Task):
            t = ev.obj
            self.unassigned.pop(t.id, None)
            self.preassigned.pop(t.id, None)
            if t.volumes:
                self.volume_set.release_task(t)
            if t.node_id and t.node_id in self.node_infos:
                info = self.node_infos[t.node_id]
                if info.remove_task(t):
                    self.encoder.mark_numeric(info)
            return True
        if isinstance(ev, (EventCreate, EventUpdate)) and isinstance(ev.obj, Node):
            self._add_or_update_node(ev.obj)
            return True
        if isinstance(ev, EventDelete) and isinstance(ev.obj, Node):
            self._remove_node(ev.obj.id)
            return True
        from ..api.objects import Volume as _Volume

        if isinstance(ev, (EventCreate, EventUpdate)) and isinstance(ev.obj, _Volume):
            self.volume_set.add_or_update_volume(ev.obj)
            return True
        if isinstance(ev, EventDelete) and isinstance(ev.obj, _Volume):
            self.volume_set.remove_volume(ev.obj.id)
            return True
        return False

    # ------------------------------------------------------------------- run
    def run(self):
        ch = self._setup()
        # pre-existing work ticks after the first debounce, inside the
        # loop's failure handling: a device fault on the first wave is
        # counted and retried like any other, never a dead thread
        dirty_since: float | None = (
            time.monotonic() if self.unassigned or self.preassigned
            else None)
        try:
            while not self._stop.is_set():
                timeout = 0.2
                if dirty_since is not None:
                    timeout = COMMIT_DEBOUNCE
                try:
                    ev = ch.get(timeout=timeout)
                except TimeoutError:
                    ev = None
                except ChannelClosed:
                    return
                now = time.monotonic()
                if ev is not None:
                    # one span per burst handled between ticks (armed
                    # only); files as tick_stage_seconds{stage=drain}
                    sp = None
                    if trace.enabled():
                        sp = trace.span("sched.drain")
                    handled = 1
                    needs = self._handle(ev)
                    if isinstance(ev, EventCommit):
                        needs = bool(self.unassigned or self.preassigned)
                    if needs and dirty_since is None:
                        dirty_since = now
                    # drain cheaply before ticking
                    continue_draining = True
                    while continue_draining:
                        try:
                            nxt = ch.try_get()
                        except ChannelClosed:
                            return
                        if nxt is None:
                            continue_draining = False
                        else:
                            handled += 1
                            if self._handle(nxt) and dirty_since is None:
                                dirty_since = now
                    if sp is not None:
                        sp.end(events=handled)
                if dirty_since is not None and (
                        ev is None or now - dirty_since >= MAX_LATENCY):
                    # debounce elapsed with no new event, or max latency hit
                    try:
                        self.tick()
                        # an in-flight pipelined wave must complete even if
                        # no further event arrives: stay dirty so the next
                        # debounce fires the completing tick
                        dirty_since = (time.monotonic()
                                       if self._inflight is not None
                                       else None)
                    except Exception as exc:
                        self._inflight = None
                        if self._resident is not None:
                            # the device carry may have folded a tick the
                            # host never applied: resync from host state
                            self._resident.invalidate()
                        if self._commit_worker is not None:
                            # a worker exception propagated into this
                            # tick; the invalidate above plus the event-
                            # plane's ASSIGNED echoes heal the partial
                            # commit — un-poison the plane for the retry
                            worker_died = self._commit_worker.failed
                            # overlap can put TWO heavies on the plane: a
                            # crash in the older one makes the worker
                            # DROP the queued younger one un-run (its
                            # fold is then backed by nothing, and its
                            # finally tail never removed its ids). The
                            # recorded-unclean heal only covers the wave
                            # the worker crashed ON — any leftover
                            # exclusion entry at a died-worker heal means
                            # a dropped heavy, which needs the blanket
                            # poison (a crashed job removes its own ids
                            # in its finally).
                            dropped_heavy = (worker_died
                                             and bool(
                                                 self._pending_commit_ids))
                            self._commit_worker.reset()
                            # poisoned-and-dropped jobs never ran their
                            # exclusion-removal tail; the pool they
                            # guarded is being re-attempted wholesale
                            self._pending_commit_ids.clear()
                            if self._worker_unclean is not None:
                                self._heal_unclean()
                                if dropped_heavy:
                                    self.encoder.poison_all_numeric()
                            elif worker_died:
                                # the worker died before recording which
                                # wave it carried (crash pre-job): any
                                # row may hold an unbacked optimistic
                                # fold — poison them all (chaos-harness
                                # regression). Gated on an ACTUAL worker
                                # failure: a transient propose error must
                                # not tax the next tick with a full
                                # numeric re-encode
                                self.encoder.poison_all_numeric()
                        from ..utils.leadership import leadership_lost

                        if leadership_lost(exc):
                            log.info("scheduler: leadership lost; stopping")
                            return
                        # a propose can fail transiently (quorum loss); the
                        # unassigned pool is preserved and the max-latency
                        # path retries even with no new events — the loop
                        # must survive
                        self.tick_failures += 1
                        log.exception("scheduler: tick failed; will retry")
                        dirty_since = time.monotonic()
        finally:
            try:
                if self._inflight is not None:
                    self.flush_pipeline()
            except Exception:
                self._inflight = None
                if self._resident is not None:
                    self._resident.invalidate()
            self.store.queue.stop_watch(ch)

    # ------------------------------------------------------------------ tick
    def tick(self):
        self.ticks += 1
        # trace-plane root: stage spans (encode/dispatch/device_sync/
        # barrier/commit) nest under it implicitly; the NOOP singleton
        # when disarmed — no allocation on the hot path
        with trace.span("sched.tick", n=self.ticks):
            if self._inflight is not None:
                self._tick_pipelined()
                return
            # the serial path reads and mutates host state end to end:
            # retire any heavy commit still riding the async plane first
            # (worker exceptions re-raise here, into the guarded tick)
            self._drain_commit_plane()
            if self.preassigned:
                self._process_preassigned()
            self._schedule_backlog()

    def _schedule_backlog(self):
        """One scheduling pass over the unassigned pool (the serial tick
        body). In pipeline mode a jax-shaped wave dispatches and returns
        with the wave in flight; anything else commits synchronously."""
        if not self.unassigned:
            return
        groups = self._group_unassigned()
        if not groups:
            return
        with trace.span("tick.encode", groups=len(groups)):
            problem = self.encoder.encode(list(self.node_infos.values()),
                                          groups,
                                          volume_set=self.volume_set)
        # the scan component (sort + fingerprint compare; ~0 on the
        # tracked zero-scan path) files as its own stage for the
        # tick_stage_seconds histogram (armed only; one truthiness test
        # disarmed)
        trace.rec("tick.dirty_scan", self.encoder.last_scan_s)
        use_jax = self._use_jax(problem)
        if use_jax and self.backend == "auto" \
                and len(problem.node_ids) <= COLD_CPU_NODES \
                and not self._cold_cpu_done \
                and (self._resident is None
                     or self._resident.needs_full_upload(problem)):
            # cold-start policy: no usable device state — the first wave
            # is cheaper on the CPU oracle than behind a blocking cold
            # upload + counts RTT; the next wave warms the device (the
            # one-shot flag stops the CPU tick's own invalidate() from
            # re-triggering this forever)
            use_jax = False
            self._cold_cpu_done = True
        if use_jax:
            if self._resident is None:
                from ..ops.resident import ResidentPlacement

                self._resident = ResidentPlacement(
                    self.encoder, mesh=self._make_mesh())
            self._cold_cpu_done = False      # device state is warming
            if self.pipeline:
                # dispatch only: the counts D2H rides the link through the
                # debounce window; the next tick completes the wave
                with trace.span("tick.dispatch"):
                    h = self._resident.schedule_async(problem)
                ids = frozenset(t.id for g in groups for t in g.tasks)
                self._inflight = (problem, h, ids)
                return
            # blocking schedule: the counts pull inside is the one real
            # device sync of this tick (one span per burst)
            with trace.span("tick.device_sync"):
                counts = self._resident.schedule(problem)
        else:
            with trace.span("tick.cpu_fill"):
                counts = cpu_schedule_encoded(problem)
            if self._resident is not None:
                # the device copy missed this tick's fold: resync on the
                # next jax tick
                self._resident.invalidate()
        with trace.span("tick.commit"):
            orders = materialize_orders(problem, counts)
            self._apply_decisions(problem, orders, counts)

    def _make_mesh(self):
        """Resolve the configured mesh (backend="mesh" / mesh=) to a
        jax.sharding.Mesh, or None for single-device."""
        mesh = self.mesh
        if mesh is None and self.backend != "mesh":
            return None
        if mesh is None or isinstance(mesh, int):
            import jax

            from ..parallel.mesh import make_mesh

            n = mesh if mesh is not None else len(jax.devices())
            # the resident state's node buckets are powers of two, so the
            # sharded axis must be one too: round down (a 6-device host
            # runs a 4-device mesh rather than crashing on upload)
            chosen = 1 << (max(n, 1).bit_length() - 1)
            if chosen != n:
                log.info("scheduler: mesh backend using %d of %d visible "
                         "devices (node axis must be a power of two)",
                         chosen, n)
            else:
                log.info("scheduler: mesh backend over %d devices", chosen)
            mesh = make_mesh(chosen)
        return mesh

    def _use_jax(self, problem) -> bool:
        total_tasks = int(problem.n_tasks.sum())
        return (self.backend in ("jax", "mesh")
                or (self.backend == "auto"
                    and total_tasks * max(len(problem.node_ids), 1)
                    >= self.jax_threshold))

    def _tick_pipelined(self, allow_retry: bool = True):
        """Complete the in-flight wave and keep the pipeline primed: pull
        counts, fold (optimistically), dispatch the NEXT wave, then commit
        the completed one under the new wave's transfer (ops/pipeline.py
        order). An unclean commit abandons both the fold and any stale
        next dispatch — fingerprint deltas re-encode the touched rows.

        allow_retry=False (flush/stop path): a conflicted or discarded
        wave is NOT re-attempted, so the drain terminates instead of
        dispatching fresh waves forever."""
        problem, h, prev_ids = self._inflight
        self._inflight = None
        worker = self._commit_worker
        overlap = False
        if worker is not None:
            # encode/commit overlap gate (round 6) — O(1): with a
            # TRACKED-clean encoder, no preassigned work, no volumes (the
            # in-tx volume choose mutates the VolumeSet this tick's
            # encode would read) and a healthy plane, nothing below reads
            # state the riding heavy commit writes — the barrier is
            # skipped and the previous wave's walk/write-back overlaps
            # this tick's fold + zero-scan encode + dispatch. An unclean
            # outcome recorded mid-overlap is caught at the NEXT
            # non-overlap barrier, which discards the wave primed on the
            # lying fold — the pre-existing one-wave-late heal semantics.
            overlap = (self.encoder.tracked and not self.preassigned
                       and self._worker_unclean is None
                       and not worker.failed
                       and not self.volume_set.volumes
                       and self.encoder.nodes_clean(
                           self.node_infos.values()))
            # async plane: pull FIRST — the blocking transfer wait
            # releases the GIL, which is when the previous wave's heavy
            # commit runs — then (overlap off) barrier before any
            # host-state read.
            with trace.span("tick.device_sync"):
                counts = h.get()
            if overlap and (worker.failed
                            or self._worker_unclean is not None):
                overlap = False     # plane turned unhealthy mid-pull
            if not overlap:
                with trace.span("tick.barrier"):
                    worker.barrier()    # worker exceptions re-raise here
                self._pending_commit_ids.clear()
                if self._worker_unclean is not None:
                    # the PREVIOUS wave's commit was unclean, and THIS
                    # wave was primed on its lying fold: heal (poison +
                    # resident resync) and discard this wave un-folded —
                    # its tasks are still in the unassigned pool, so
                    # attempt them fresh against the healed state (no
                    # pool-changed gate: a discarded wave was never
                    # attempted, so going idle here would wedge it)
                    self._heal_unclean()
                    if self.preassigned:
                        self._process_preassigned()
                    if allow_retry and self.unassigned:
                        self._schedule_backlog()
                    return
                if self.preassigned:
                    self._process_preassigned()
            # overlap path: the gate proved no preassigned work and no
            # recorded unclean wave; a record landing in the remaining
            # window is healed at the next non-overlap barrier (which
            # discards the wave primed below) — never concurrently with
            # a still-riding heavy.
        else:
            if self.preassigned:
                # preassigned (global-service) tasks never touch the
                # encoded problem; under sustained pipelined load this
                # is their only slot (the serial path's call is short-
                # circuited). Their add_task bumps flip nodes_clean,
                # which correctly forces the touched rows to re-encode
                # before the next dispatch.
                self._process_preassigned()
            with trace.span("tick.device_sync"):
                counts = h.get()
        with trace.span("tick.fold"):
            folded = self.encoder.fold_counts(problem, counts)
            if folded:
                self._resident.after_apply(problem, counts)
            else:
                self._resident.invalidate()

        if worker is not None and folded and overlap:
            # overlap: the heavy half is submitted BEFORE the prime, so
            # the zero-scan encode below runs concurrently with the
            # walk/write-back (the pool race is closed by the exclusion
            # set _submit_heavy maintains)
            self.overlapped_commits += 1
            try:
                self._submit_heavy(problem, counts, prev_ids)
            except BaseException:
                # the riding heavy failed inside the overlap window
                # (post-gate): submit refused THIS wave, whose fold
                # already ran and whose add_task walk will never run —
                # poison its placed-on rows so the run-loop heal
                # re-derives them (the recorded-unclean heal only
                # covers the wave the worker crashed on)
                self.encoder.force_numeric_reencode(
                    np.flatnonzero(counts.sum(axis=0)))
                raise

        # next wave: everything unassigned that is NOT still uncommitted
        # in the wave being completed (no double placement) NOR in a wave
        # whose heavy commit may still be riding the plane
        if (folded and self.pipeline
                and self.encoder.nodes_clean(self.node_infos.values())):
            exclude = prev_ids
            pending = tuple(self._pending_commit_ids)
            if pending:
                exclude = frozenset().union(prev_ids, *pending)
            next_groups = self._group_unassigned(exclude=exclude)
            # CPU-shaped waves skip the prime entirely (the encode would
            # be discarded and redone by the fallthrough below)
            total_next = sum(len(g.tasks) for g in next_groups)
            if next_groups and (
                    self.backend in ("jax", "mesh")
                    or total_next * max(len(self.node_infos), 1)
                    >= self.jax_threshold):
                with trace.span("tick.encode", groups=len(next_groups)):
                    p_next = self.encoder.encode(
                        list(self.node_infos.values()), next_groups,
                        volume_set=self.volume_set)
                trace.rec("tick.dirty_scan", self.encoder.last_scan_s)
                if self._use_jax(p_next):
                    with trace.span("tick.dispatch"):
                        h_next = self._resident.schedule_async(p_next)
                    ids = frozenset(
                        t.id for g in next_groups for t in g.tasks)
                    self._inflight = (p_next, h_next, ids)

        if worker is not None and folded:
            # heavy half rides the commit plane: materialization, store
            # write-back, the add_task walk, the restamp — retired by
            # the next barrier; an unclean outcome heals there too.
            # Barriered order: enqueued only now, after this tick's
            # encode/dispatch stopped reading host state (the overlap
            # path submitted before the prime instead).
            if not overlap:
                self._submit_heavy(problem, counts, prev_ids)
            if self._inflight is None and self.unassigned:
                # nothing primed: the backlog must be attempted NOW
                # (wedge avoidance, same as the sync path below) — and
                # that reads the pool the worker is mutating, so retire
                # the commit first (rare when load is sustained; the
                # primed case above keeps the overlap)
                self._drain_commit_plane()
                if allow_retry and (
                        frozenset(self.unassigned) != prev_ids
                        or self._last_commit_conflicts):
                    # conflict bypass of the pool-changed gate: the
                    # commit ran BEHIND the plane, so the store write
                    # that conflicted it may already have been consumed
                    # by the event loop mid-flight — with no event left
                    # to retrigger, an identical pool would wedge. One
                    # immediate retry runs against node_infos that
                    # already include that write; a repeat conflict
                    # implies a FRESH store divergence whose event is
                    # still queued to wake the loop.
                    self._schedule_backlog()
            return
        if worker is not None and overlap:
            # the overlap path skipped the top barrier and the fold
            # failed (node set moved under us — unreachable while the
            # tracked gate pins it, but defensive): an inline commit
            # below must never run beside a riding heavy
            self._drain_commit_plane()
        with trace.span("tick.commit"):
            orders = materialize_orders(problem, counts)
            clean = self._apply_decisions(problem, orders, counts,
                                          deferred_fold=True)
        if clean:
            self.encoder.restamp_counts(problem, counts)
        else:
            # the optimistic fold lied: poison every placed-on row so the
            # next encode re-derives it from the NodeInfo objects (a node
            # whose placements ALL dropped never bumped its mutation
            # counter — without this its phantom reservations persist),
            # resync the device, and discard any dispatch built on the
            # bad fold
            self.encoder.force_numeric_reencode(
                np.flatnonzero(counts.sum(axis=0)))
            self._resident.invalidate()
            if self._inflight is not None:
                _p2, h2, _ids2 = self._inflight
                self._inflight = None
                h2.get()
        if (self._inflight is None and self.unassigned and allow_retry
                and (frozenset(self.unassigned) != prev_ids
                     or self._last_commit_conflicts)):
            # nothing primed (dirty nodes, CPU-shaped wave, unclean heal,
            # or the backlog arrived after the prime check): schedule it
            # NOW — leaving it for a future event would wedge a backlog
            # that generates no further events (chaos-test regression).
            # The pool-changed gate stops the degenerate loop: a pool
            # identical to the wave just attempted is unplaceable-as-is
            # (explanations written by _apply_decisions) and must go IDLE
            # until an event, exactly like the serial path — otherwise
            # flush_pipeline() never terminates and the run loop burns a
            # device round trip per debounce forever.
            self._schedule_backlog()

    def flush_pipeline(self):
        """Complete any in-flight wave now (stop/leadership-loss path);
        in async mode also retire the last heavy commit."""
        while self._inflight is not None:
            self._tick_pipelined(allow_retry=False)
        self._drain_commit_plane()

    def _group_unassigned(self, exclude: frozenset | None = None,
                          ) -> list[TaskGroup]:
        grouped: dict[tuple[str, int], list[Task]] = defaultdict(list)
        # list() is one C-level op (GIL-atomic): on the overlap path a
        # riding heavy commit pops committed tasks from this dict
        # concurrently — a plain .values() iteration would raise
        # "dict changed size". A popped task still in the snapshot is in
        # the exclusion set by construction (_pending_commit_ids).
        for t in list(self.unassigned.values()):
            if exclude is not None and t.id in exclude:
                continue
            sv = t.spec_version.index if t.spec_version else 0
            grouped[(t.service_id or t.id, sv)].append(t)
        out = []
        for k, ts in grouped.items():
            ts = sorted(ts, key=lambda t: t.id)
            # ids built here, while the sort has the task objects hot —
            # the wave-commit walk keys on them (TaskGroup.ids contract)
            out.append(TaskGroup(service_id=k[0], spec_version=k[1],
                                 tasks=ts, ids=[t.id for t in ts]))
        return out

    # -------------------------------------------------------------- commits
    def _batched_writes(self, items: list, write_one) -> None:
        """ONE grouped store update for `items` (round 6): `write_one(tx,
        item)` runs for every item inside a single update transaction —
        one lock hold, one table swap, one event batch — instead of one
        Batch closure + one sub-transaction per 200 items. Raft-backed
        stores keep the reference's per-entry bound: items chunk at
        MAX_CHANGES_PER_TRANSACTION and the sub-transactions pipeline
        through the group-commit plane (disjoint by construction — a
        task id appears at most once per wave write-back)."""
        if not items:
            return
        if self.store.proposer is not None:
            step = MAX_CHANGES_PER_TRANSACTION
            depth = WRITEBACK_PIPELINE_DEPTH
        else:
            step = len(items)
            depth = None
        chunks = [items[i:i + step] for i in range(0, len(items), step)]

        def batch_cb(batch):
            for chunk in chunks:
                def run_chunk(tx, chunk=chunk):
                    for item in chunk:
                        write_one(tx, item)

                batch.update_many(run_chunk, len(chunk))

        self.store.batch(batch_cb, pipeline_depth=depth)

    def _apply_decisions(self, problem, orders, counts=None,
                         deferred_fold=False) -> bool:
        """store.Batch with in-tx re-validation (scheduler.go:490-643).

        `orders` is materialize_orders output: per group (aligned with
        problem.groups) the canonical slot order of node indices; the
        group's id-sorted tasks zip with it, tasks past the end are
        unplaced.

        deferred_fold=True (pipelined path): the caller already folded the
        encoder optimistically and owns the restamp/invalidate decision —
        the return value says whether the commit was clean (exactly one
        add_task per decided placement)."""
        groups = problem.groups
        # gi -> [(committed task, node index)] for successful assignments
        applied_by_group: dict[int, list[tuple[Task, int]]] = {}
        # tasks no longer schedulable (deleted, dead, raced to assigned
        # elsewhere) — evicted from the unassigned pool after the batch;
        # conflicted decisions are NOT dropped and retry next tick
        drop: list[str] = []
        unplaced: list[tuple[Task, TaskGroup]] = []
        conflicts = [0]

        node_ids = problem.node_ids
        from ..csi.volumes import task_csi_mounts

        # flat decision list in (group, slot) order — the store write-back
        # runs it as ONE grouped transaction (round 6; _batched_writes)
        # instead of one closure + one 200-change sub-transaction slice
        # per task, keeping the exact per-task in-tx re-validation
        decisions: list[tuple] = []
        for gi, group in enumerate(groups):
            order = orders[gi]
            n_placed = len(order)
            for ti, task in enumerate(group.tasks):
                ni = int(order[ti]) if ti < n_placed else -1
                decisions.append(
                    (task, node_ids[ni] if ni >= 0 else None, ni, group, gi))

        # columnar bulk path (ISSUE 11): placed decisions without CSI
        # volume choice commit as ONE store.assign_wave — vectorized
        # in-tx re-validation against the columnar mirror, one shallow
        # patch per task instead of two tree copies, same events. CSI
        # tasks keep the object path (choose_task_volumes is a per-task
        # in-tx decision); unplaced rows keep it too (explanations).
        fast: list[tuple] = []
        slow: list[tuple] = []
        if self.columnar_writeback:
            for d in decisions:
                if d[1] is not None and not task_csi_mounts(d[0]):
                    fast.append(d)
                else:
                    slow.append(d)
        else:
            slow = decisions
        if fast:
            codes, committed = self.store.assign_wave(
                [(task.id, node_id) for task, node_id, *_ in fast],
                pipeline_depth=WRITEBACK_PIPELINE_DEPTH)
            for (task, node_id, ni, group, gi), code, cur in zip(
                    fast, codes, committed):
                if code == ASSIGN_OK:
                    applied_by_group.setdefault(gi, []).append((cur, ni))
                elif code == ASSIGN_NODE_NOT_READY:
                    conflicts[0] += 1
                else:           # missing / dead / raced: evict from pool
                    drop.append(task.id)

        def write_decision(tx, item):
            task, node_id, ni, group, gi = item
            cur = tx.get_task(task.id)
            if cur is None or cur.desired_state > TaskState.COMPLETE:
                drop.append(task.id)
                return
            if cur.status.state != TaskState.PENDING or cur.node_id:
                drop.append(task.id)
                return
            if node_id is None:
                # explanation is written in a second pass, after node
                # bookkeeping reflects this tick's sibling placements —
                # else 'insufficient resources' reads as 'all filters
                # passed'
                unplaced.append((cur, group))
                return
            node = tx.get_node(node_id)
            if node is None or node.status.state != NodeStatusState.READY:
                conflicts[0] += 1
                return  # conflicted: retried (see below)
            cur = cur.copy()
            # CSI volumes chosen at commit time, with the reservation
            # re-check the reference does in-tx (scheduler.go:533-604
            # volume availability)
            if task_csi_mounts(cur):
                chosen = self.volume_set.choose_task_volumes(cur, node)
                if chosen is None:
                    conflicts[0] += 1
                    return  # conflicted: retried (see below)
                cur.volumes = chosen
            cur.node_id = node_id
            cur.status.state = TaskState.ASSIGNED
            cur.status.message = "scheduler assigned task to node"
            cur.status.timestamp = time.time()
            tx.update(cur)
            applied_by_group.setdefault(gi, []).append((cur, ni))

        if slow:
            self._batched_writes(slow, write_decision)
        if applied_by_group and lifecycle.enabled():
            # lifecycle plane: ONE batched ASSIGNED record covering every
            # task this wave placed — never per task inside the commit
            # walk (the plane's batching contract; id assembly is gated
            # so the disarmed path allocates nothing)
            lifecycle.record_batch(
                TaskState.ASSIGNED,
                [t.id for placed in applied_by_group.values()
                 for t, _ in placed])
        # conflicted decisions stay in the pool; the serial path relies
        # on the causing store write's still-queued event to retrigger,
        # but a pipelined wave may conflict on an event consumed while
        # it was in flight — record the count so the completing tick can
        # retry the pool itself (async mode reads this post-barrier)
        self._last_commit_conflicts = conflicts[0]

        with_generic: list[tuple[str, str]] = []
        # wave-level NodeInfo bookkeeping (batch.apply_placements): the
        # per-task add_task loop was the commit's hot spot — typical big
        # waves degenerate to ~1 task per (group, node) cell, so the bulk
        # path segments per node across the whole wave. Groups with
        # generic reservations or host ports keep the full per-task path
        # inside apply_placements.
        placed_groups = []
        for gi, placed in applied_by_group.items():
            group = groups[gi]
            for task, _ni in placed:
                self.unassigned.pop(task.id, None)
            if group.tasks[0].spec.resources.reservations.generic:
                with_generic.extend(
                    (task.id, node_ids[ni]) for task, ni in placed)
            committed = [t for t, _ in placed]
            placed_groups.append(
                (group.tasks[0], committed,
                 np.fromiter((ni for _, ni in placed), np.int64,
                             len(placed)),
                 # ids built here while the committed copies are hot from
                 # the store transaction (TaskGroup.ids contract)
                 [t.id for t in committed]))
        if placed_groups:
            # row-order NodeInfo list for the walk: reuse the problem's
            # encode-time snapshot when it is still current (tracked
            # encoders bump infos_seq on any row-object swap — replaced
            # node, set change — so the O(1) stamp check is sound; the
            # barrier discipline keeps marks out of the commit window).
            # Stale or untracked: rebuild from the live map, where a
            # removed node correctly yields None (skipped, uncounted —
            # the unclean heal covers it).
            infos = problem.row_infos
            if (infos is None or not self.encoder.tracked
                    or problem.infos_seq != self.encoder.infos_seq):
                infos = [self.node_infos.get(nid) for nid in node_ids]
            n_added = apply_placements(infos, placed_groups)
        else:
            n_added = 0
        # fold our own placements back into the encoder's cached rows
        # (vectorized) iff every decided placement landed as exactly one
        # add_task; otherwise let the fingerprint delta re-encode the
        # touched rows next tick (conflicts/drops are rare)
        clean = counts is not None and n_added == int(counts.sum())
        if deferred_fold:
            pass    # pipelined caller folded pre-commit and owns the rest
        elif clean:
            folded = self.encoder.apply_counts(problem, counts)
            if self._resident is not None:
                if folded:
                    self._resident.after_apply(problem, counts)
                else:
                    self._resident.invalidate()
        elif counts is not None:
            if self._resident is not None:
                # fingerprint deltas will re-encode the touched rows next
                # tick, but the device carry already folded THIS tick's
                # full counts: resync from host
                self._resident.invalidate()
            if self.encoder.tracked:
                # the zero-scan path never reads those fingerprints: the
                # placed-on rows must also land in the mark feed, or the
                # partial add_task walk stays invisible to the next encode
                for r in np.flatnonzero(counts.sum(axis=0)).tolist():
                    info = self.node_infos.get(node_ids[r])
                    if info is not None:
                        self.encoder.mark_numeric(info)
        if with_generic:
            # persist which named/discrete generic resources were granted
            # (reference nodeinfo.go:132-137 stamps AssignedGenericResources
            # on the task before commit; we claim post-commit and follow up)
            def write_generic(tx, item):
                task_id, node_id = item
                cur = tx.get_task(task_id)
                info = self.node_infos.get(node_id)
                if cur is None or info is None:
                    return
                cur = cur.copy()
                cur.assigned_generic_resources = {
                    kind: (sorted(named), count)
                    for kind, (named, count)
                    in info.assigned_generic(task_id).items()
                }
                tx.update(cur)

            self._batched_writes(with_generic, write_generic)
        for task_id in drop:
            self.unassigned.pop(task_id, None)

        if unplaced:
            # second pass: explanations against bookkeeping that now includes
            # this tick's placements, written only on change so identical
            # failures don't retrigger the commit debounce forever
            # explanations computed BEFORE the grouped transaction: the
            # filter-pipeline walk is O(nodes) per group and must not run
            # under the store's update lock
            explain_cache: dict[tuple[str, int], str] = {}
            for _task, group in unplaced:
                if group.key not in explain_cache:
                    explain_cache[group.key] = self._explain(group)

            def write_explanation(tx, item):
                task, group = item
                explanation = explain_cache[group.key]
                cur = tx.get_task(task.id)
                if cur is None or cur.status.state != TaskState.PENDING:
                    return
                if cur.status.err == explanation:
                    return
                cur = cur.copy()
                cur.status.message = "scheduler: no suitable node"
                cur.status.err = explanation
                cur.status.timestamp = time.time()
                tx.update(cur)

            self._batched_writes(unplaced, write_explanation)
        # everything else (no-suitable-node, conflicted commits) stays in
        # self.unassigned; node/task events retrigger the tick
        return clean

    def _explain(self, group: TaskGroup) -> str:
        pipeline = Pipeline(self.volume_set)
        pipeline.set_task(group.tasks[0])
        for info in self.node_infos.values():
            pipeline.process(info)
        return pipeline.explain() or "no nodes available"

    # --------------------------------------------------------- preassigned
    def _process_preassigned(self):
        """Global-service tasks arrive with node_id set; validate fit only
        (reference processPreassignedTasks/taskFitNode, scheduler.go:398-426)."""
        tasks = list(self.preassigned.values())
        decided: list[tuple[Task, bool]] = []
        pipeline = Pipeline(self.volume_set)
        for t in tasks:
            info = self.node_infos.get(t.node_id)
            if info is None:
                continue  # wait for node
            pipeline.set_task(t)
            decided.append((t, pipeline.process(info)))

        # lifecycle plane: collect ids INSIDE the tx, only for writes
        # that actually landed (same discipline as the wave path's
        # applied_by_group — a task deleted mid-decision must not file a
        # phantom ASSIGNED that then reads as "stuck" forever)
        applied: list[str] | None = [] if lifecycle.enabled() else None

        def write_preassigned(tx, item):
            task, fits = item
            cur = tx.get_task(task.id)
            if cur is None or cur.status.state != TaskState.PENDING:
                return
            if fits:
                cur = cur.copy()
                cur.status.timestamp = time.time()
                cur.status.state = TaskState.ASSIGNED
                cur.status.message = (
                    "scheduler confirmed task can run on preassigned node")
                tx.update(cur)
                if applied is not None:
                    applied.append(cur.id)
            else:
                # keep PENDING and retry later — transient pressure
                # (resources, ports) may clear (reference
                # scheduler.go:654-661 only records Status.Err)
                err = "preassigned node does not satisfy filters"
                if cur.status.err != err:
                    cur = cur.copy()
                    cur.status.timestamp = time.time()
                    cur.status.err = err
                    tx.update(cur)

        self._batched_writes(decided, write_preassigned)
        if applied:
            lifecycle.record_batch(TaskState.ASSIGNED, applied)
        for task, fits in decided:
            if fits:
                self.preassigned.pop(task.id, None)
                info = self.node_infos.get(task.node_id)
                if info and info.add_task(task):
                    self.encoder.mark_numeric(info)
            # non-fitting tasks stay in self.preassigned for retry
