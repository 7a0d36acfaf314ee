"""RaftProposer: bridges MemoryStore transactions onto raft consensus.

The reference's write path (SURVEY.md §3.4): store.update collects a
changelist → proposer.ProposeValue blocks until the entry commits → the
registered wait triggers the in-memory commit on the leader; followers (and
restart replay) apply the same actions via ApplyStoreActions. Object
versions are stamped with the raft entry index on every replica, so
version-checked updates behave identically cluster-wide.
"""
from __future__ import annotations

import bisect
import threading
from typing import Callable

import time

from ..analysis.lockgraph import make_lock
from ..api.objects import Version
from ..utils import trace
from ..utils.identity import new_id
from ..utils.metrics import histogram
from .messages import ERR_LEADERSHIP_LOST, ERR_NOT_LEADER, Entry
from .node import RaftNode

PROPOSE_TIMEOUT = 30.0

# reference: swarm_raft_transaction_latency (raft.go:204-209)
_propose_latency = histogram(
    "swarm_raft_transaction_latency_seconds",
    "raft proposal submit→commit duration")


class ProposeError(Exception):
    pass


class LeadershipLost(ProposeError):
    """The proposal failed because this node is not (or stopped being) the
    raft leader — distinct from transient failures like a quorum-loss
    timeout, which may resolve while still leading. Leader-only component
    threads treat this as a clean-shutdown signal
    (utils/leadership.leadership_lost)."""


# the demotion markers RaftNode builds its propose-callback errors from
# (messages.ERR_*); matched HERE only, so callers get a structured
# exception and a rewording can't desynchronize producer and classifier
_NOT_LEADER_MARKERS = (ERR_NOT_LEADER, ERR_LEADERSHIP_LOST)


def _classify(err: str) -> type[ProposeError]:
    return (LeadershipLost
            if any(m in err for m in _NOT_LEADER_MARKERS) else ProposeError)


class PendingProposal:
    """Handle for a pipelined proposal (propose_async): the caller may keep
    up to depth-K of these in flight; raft commits them in log order and
    resolves each handle from the worker thread. `wait()`/`result()` give
    the blocking API its exact semantics back."""

    def __init__(self, request_id: str):
        self.request_id = request_id
        self._done = threading.Event()
        self._ok = False
        self._err = ""
        self._started = time.monotonic()

    def _resolve(self, ok: bool, err: str):
        self._ok = ok
        self._err = err
        self._done.set()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: float | None = None) -> bool:
        return self._done.wait(timeout)

    def result(self, timeout: float = PROPOSE_TIMEOUT) -> None:
        """Block until commit; raise the same typed errors propose_value
        raises (ProposeError / LeadershipLost)."""
        if not self._done.wait(timeout):
            raise ProposeError("proposal timed out")
        _propose_latency.observe(time.monotonic() - self._started)
        if not self._ok:
            err = self._err or "proposal dropped"
            raise _classify(err)(err)


class RaftProposer:
    def __init__(self, node: RaftNode, store=None):
        self.node = node
        self.store = store
        self._pending: dict[str, Callable[[int], None]] = {}
        self._lock = make_lock('raft.proposer.lock')
        node.apply_entry = self._apply_entry
        node.snapshot_state = self._snapshot_state
        node.restore_state = self._restore_state

    def attach_store(self, store):
        """Wire the store, then replay any persisted raft state into it —
        construct the node with auto_recover=False for this to work."""
        self.store = store
        self.node.recover()

    def _snapshot_state(self):
        return self.store.save() if self.store is not None else None

    def _restore_state(self, snap):
        if self.store is not None and snap is not None:
            self.store.restore(snap)

    # ------------------------------------------------------ Proposer protocol
    def propose_async(self, actions,
                      commit_cb: Callable[..., None]) -> PendingProposal:
        """Non-blocking propose: returns a PendingProposal immediately so
        the store can pipeline transactions at depth K against the raft
        group-commit plane (K proposals share one WAL fsync + one
        replication flush instead of paying one each). On commit the
        registered commit_cb runs on the raft worker thread, in log
        order; failure resolves the handle without running commit_cb."""
        req_id = new_id()
        handle = PendingProposal(req_id)
        with self._lock:
            self._pending[req_id] = commit_cb

        # trace plane: the proposal's span — submit→commit-resolve, under
        # the caller's open span (the component or stage that proposes).
        # Its ctx rides the staged Entry (and therefore replication and
        # the WAL), so every replica's fsync/apply spans join this trace.
        # Armed only: zero allocation on the disarmed propose path.
        actions = list(actions)
        sp = None
        if trace.enabled():
            sp = trace.start("raft.propose", actions=len(actions))

        def on_result(ok: bool, err: str):
            if not ok:
                with self._lock:
                    self._pending.pop(req_id, None)
            if sp is not None:
                sp.end(ok=ok)
            handle._resolve(ok, err)

        self.node.propose(actions, req_id, on_result,
                          trace_ctx=sp.ctx() if sp is not None else None)
        return handle

    def propose_value(self, actions, commit_cb: Callable[..., None]) -> None:
        handle = self.propose_async(actions, commit_cb)
        try:
            handle.result(PROPOSE_TIMEOUT)
        except ProposeError:
            with self._lock:
                self._pending.pop(handle.request_id, None)
            raise

    def get_version(self) -> Version:
        return Version(self.node.commit_index)

    def changes_between(self, from_v: Version, to_v: Version) -> list:
        node = self.node
        # grab the list reference once: the raft worker thread replaces it
        # wholesale on truncation/compaction (our reference stays a
        # consistent prefix) and only ever appends in place
        entries = node.log
        first = entries[0].index if entries else node.first_index
        if from_v.index + 1 < first:
            # entries below `first` were compacted into a snapshot; a partial
            # answer would silently diverge the replaying watcher
            raise ProposeError(
                f"changes from {from_v.index} compacted (log starts at {first})")
        # entry indexes are sorted and dense: bisect to the requested
        # window instead of scanning the whole log per watcher resync
        lo = bisect.bisect_right(entries, from_v.index,
                                 key=lambda e: e.index)
        hi = bisect.bisect_right(entries, to_v.index, key=lambda e: e.index)
        return [e.data for e in entries[lo:hi]
                if e.data is not None and e.kind == 0]

    # --------------------------------------------------------------- applying
    def _apply_entry(self, entry: Entry) -> None:
        """Runs on every replica in commit order (raft worker thread)."""
        cb = None
        if entry.request_id:
            with self._lock:
                cb = self._pending.pop(entry.request_id, None)
        if cb is not None:
            # leader fast path: the waiting transaction commits its own
            # buffered writes, stamped with this entry's index
            cb(version_index=entry.index)
        elif self.store is not None and entry.data is not None:
            # follower / replay path
            self.store.apply_store_actions(entry.data,
                                           version_index=entry.index)
