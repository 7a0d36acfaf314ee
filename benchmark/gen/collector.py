"""Watch collector: stamps every task at its commits to NEW, PENDING and
ASSIGNED, from the store's own event plane (no scans, no polling).

The idea is `swarmkit_tpu.cmd.swarmbench.StartupCollector`'s: subscribe to
task events and stamp each task at the first event that shows it in a state.
The store publishes a transaction's events only after raft has committed it
(and the leader's WAL has been fsynced), and stamps the committed object's
`meta.created_at` / `meta.updated_at` with the commit's wall time, so the
stamps here are commit times on the host clock (`time.time()`), not the time
this thread got round to the event.
"""
from __future__ import annotations

import threading
from collections import defaultdict


class Collector:
    """Per-task commit stamps and per-service ASSIGNED counts."""

    def __init__(self, store):
        from swarmkit_tpu.api.objects import EventCreate, EventDelete, Task
        from swarmkit_tpu.api.types import TaskState

        self._create, self._delete, self._task = EventCreate, EventDelete, Task
        self._pending, self._assigned = TaskState.PENDING, TaskState.ASSIGNED
        self.new: dict[str, float] = {}
        self.pending: dict[str, float] = {}
        self.assigned: dict[str, float] = {}
        self.node: dict[str, str] = {}
        self.service_of: dict[str, str] = {}
        self.by_service: dict[str, list[str]] = defaultdict(list)
        self.assigned_by_service: dict[str, int] = defaultdict(int)
        self.events = 0
        self._admitted: set[str] = set()
        self.admitted_assigned = 0
        self.cond = threading.Condition()
        self._store = store
        self._ch = store.queue.watch(
            lambda ev: isinstance(getattr(ev, "obj", None), Task), limit=None)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._pump, daemon=True,
                                        name="bench-collector")
        self._thread.start()

    def admit(self, service_id: str) -> None:
        """Count this service's ASSIGNED tasks into `admitted_assigned`."""
        with self.cond:
            if service_id not in self._admitted:
                self._admitted.add(service_id)
                self.admitted_assigned += self.assigned_by_service[service_id]

    def _pump(self) -> None:
        from swarmkit_tpu.store.watch import ChannelClosed

        ch = self._ch
        while not self._stop.is_set():
            try:
                if not ch.wait_any(timeout=0.5):
                    continue
            except ChannelClosed:
                return
            batch = ch.drain()
            with self.cond:
                for ev in batch:
                    self._feed(ev)
                self.cond.notify_all()

    def _feed(self, ev) -> None:
        if isinstance(ev, self._delete):
            return
        t = ev.obj
        tid = t.id
        self.events += 1
        if isinstance(ev, self._create) and tid not in self.new:
            self.new[tid] = t.meta.created_at
            self.service_of[tid] = t.service_id
            self.by_service[t.service_id].append(tid)
        state = t.status.state
        if state >= self._pending and tid not in self.pending:
            self.pending[tid] = t.meta.updated_at
        if state >= self._assigned and tid not in self.assigned \
                and t.node_id:
            self.assigned[tid] = t.meta.updated_at
            self.node[tid] = t.node_id
            sid = t.service_id
            self.assigned_by_service[sid] += 1
            if sid in self._admitted:
                self.admitted_assigned += 1

    def wait_for(self, predicate, timeout: float) -> bool:
        """Block until predicate() holds (checked under the lock after every
        event batch) or the timeout passes."""
        with self.cond:
            return self.cond.wait_for(predicate, timeout)

    def close(self) -> None:
        self._stop.set()
        self._store.queue.stop_watch(self._ch)
        self._thread.join(timeout=5.0)
