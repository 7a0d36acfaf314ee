"""Raft consensus node.

A from-scratch re-derivation of the consensus behavior the reference gets
from vendored etcd/raft plus its own wrapper (manager/state/raft/raft.go):
leader election with randomized timeouts, log replication, commit-index
advancement by quorum match, snapshot install for lagging followers, single
-step membership changes, a wait registry correlating proposals with commit
callbacks (wait.go:8-77), and leadership-change notification that the
manager uses to start/stop leader-only components.

Architecture notes (tpu-first build):
  * step model: every input — network message, clock tick, proposal — is a
    queued event processed by one worker thread, so the core is single
    -threaded and deterministic under the fake-clock test harness
    (mirroring the reference's NodeOptions.ClockSource tier-2 strategy);
  * the batched commit math (quorum tally over a simulated manager mesh)
    also exists as the TPU kernel in ops/raft_replay.py — used for
    benchmark-scale log replay, while this class owns protocol correctness.
"""
from __future__ import annotations

import logging
import queue
import random
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

from ..utils import failpoints, trace
from ..utils.metrics import counter_family

from .messages import (
    ENTRY_CONF_CHANGE,
    ENTRY_NORMAL,
    ERR_LEADERSHIP_LOST,
    ERR_NOT_LEADER,
    AppendEntries,
    AppendResponse,
    ConfChange,
    Entry,
    InstallSnapshot,
    SnapshotAck,
    SnapshotChunk,
    VoteRequest,
    VoteResponse,
)

log = logging.getLogger("swarmkit_tpu.raft")

FOLLOWER, CANDIDATE, LEADER = "follower", "candidate", "leader"

# group commit: the worker drains up to this many inbox items per loop
# iteration and performs ONE Ready flush for the whole batch — one WAL
# append + fsync, one dirty-gated hardstate save, one coalesced
# AppendEntries per peer, one commit-advance + apply pass (the
# Ready/Advance batching the reference gets from etcd/raft)
MAX_READY_BATCH = 256

MAX_ENTRIES_PER_APPEND = 64
# pipelined replication: optimistic appends may run this many messages
# ahead of the follower's last ack (reference MaxInflightMsgs: 256,
# manager/state/raft/raft.go:490); the per-peer entry window is
# MAX_INFLIGHT_APPENDS * MAX_ENTRIES_PER_APPEND
MAX_INFLIGHT_APPENDS = 256
# streamed snapshot installs (reference transport/peer.go:26-142 streams
# large messages instead of one oversized gRPC frame)
SNAPSHOT_CHUNK_BYTES = 256 * 1024
# ticks before an unacked streamed snapshot is re-sent (the follower may
# have lost chunks; until then the peer is paused, not re-blasted).
# Kept for back-compat derivation; the live deadline is CLOCK-based
# (SNAPSHOT_RESEND_SECONDS / the snapshot_resend_seconds ctor param) so
# chunk-loss schedules replay exactly under a FakeClock (ISSUE 18).
SNAPSHOT_RESEND_TICKS = 50
# seconds before an unacked streamed snapshot suffix is re-sent — the
# tick-count constant above at the daemon's 0.2 s tick cadence
SNAPSHOT_RESEND_SECONDS = SNAPSHOT_RESEND_TICKS * 0.2
# follower-side reassembly cap: a stream whose DECLARED size
# (total × chunk bytes) exceeds this is rejected outright — a buggy or
# deposed leader must not balloon follower memory with orphan chunk maps
SNAPSHOT_STREAM_MAX_BYTES = 1 << 30

# recovery-plane event counters (ISSUE 18): process-global family so the
# events ride registry_snapshot() into the PR 15 telemetry rollup; exact
# per-node assertions use the RaftNode snap_* ints instead
_snap_events = counter_family(
    "swarm_raft_snapshot_total",
    "streamed-snapshot recovery events (chunk sent/resent, suffix "
    "resume, chunk rejected, install)",
    ("event",))
# wedge-triggered leadership transfers are rate limited (reference
# raft.go:569-604 caps transfers at one per minute). Expressed in ticks
# so the deterministic fake-clock harness can drive expiry; at the
# daemon's 0.2 s tick this is one minute.
TRANSFER_MIN_TICKS = 300

# Read-lease clock-skew margin (ISSUE 13): a follower discounts every
# lease grant by this fraction before trusting it, so bounded clock-RATE
# drift between leader and follower cannot stretch a lease past the
# leader's guarantee window. 10% covers drift orders of magnitude worse
# than real hardware exhibits over a ~1 s lease.
READ_LEASE_SKEW = 0.1


class NotLeader(Exception):
    def __init__(self, leader_id: int | None, leader_addr: str | None = None):
        super().__init__(f"not the leader (leader={leader_id})")
        self.leader_id = leader_id
        self.leader_addr = leader_addr


class ProposalDropped(Exception):
    pass


@dataclass
class Peer:
    raft_id: int
    node_id: str
    addr: str


@dataclass
class _SnapPending:
    """Leader-side progress of one streamed snapshot install (etcd
    ProgressStateSnapshot analogue, resumable): `acked` is the highest
    CONTIGUOUS chunk seq the follower reported via SnapshotAck, and
    `deadline` (clock.monotonic seconds) is when an unacked stream gets
    its missing SUFFIX re-sent — never the whole blob."""

    snap_idx: int
    deadline: float
    acked: int = -1


class RaftNode:
    def __init__(
        self,
        raft_id: int,
        transport,
        storage=None,
        apply_entry: Callable[[Entry], None] | None = None,
        snapshot_state: Callable[[], Any] | None = None,
        restore_state: Callable[[Any], None] | None = None,
        on_leadership: Callable[[bool], None] | None = None,
        election_tick: int = 10,
        heartbeat_tick: int = 1,
        snapshot_interval: int = 1000,
        rng: random.Random | None = None,
        auto_recover: bool = True,
        lease_duration: float = 0.0,
        clock=None,
        snapshot_resend_seconds: float = SNAPSHOT_RESEND_SECONDS,
        snap_stream_max_bytes: int = SNAPSHOT_STREAM_MAX_BYTES,
    ):
        self.id = raft_id
        self.transport = transport
        self.storage = storage
        self.apply_entry = apply_entry or (lambda e: None)
        self.snapshot_state = snapshot_state or (lambda: None)
        self.restore_state = restore_state or (lambda s: None)
        self.on_leadership = on_leadership or (lambda is_leader: None)
        # fired (from a fresh thread) when this node learns of its OWN
        # removal from the membership — by applying the conf change, or
        # from a peer's removed-member reply (notify_removed). The
        # reference surfaces this as ErrMemberRemoved to
        # node.superviseManager, which demotes
        self.on_removed: Callable[[], None] | None = None
        # raft ids of members REMOVED from this cluster: peers answer
        # their messages with the removed marker so a member demoted
        # while down learns its fate when it comes back
        # (reference manager/state/raft/membership ErrMemberRemoved)
        self.removed_ids: set[int] = set()
        self._self_removed = False
        self.election_tick = election_tick
        self.heartbeat_tick = heartbeat_tick
        self.snapshot_interval = snapshot_interval
        self._rng = rng or random.Random()

        # persistent state
        self.term = 0
        self.voted_for: int | None = None
        self.log: list[Entry] = []
        self.first_index = 1          # index of log[0] (post-snapshot base)
        self.snapshot_index = 0
        self.snapshot_term = 0

        # volatile
        self.role = FOLLOWER
        self.leader_id: int | None = None
        self.commit_index = 0
        self.last_applied = 0
        self.members: dict[int, Peer] = {}
        self.next_index: dict[int, int] = {}
        self.match_index: dict[int, int] = {}
        self.votes: set[int] = set()
        self.election_elapsed = 0
        self.heartbeat_elapsed = 0
        self._randomized_timeout = self._next_timeout()

        self._waits: dict[str, Callable[[bool, str], None]] = {}
        self._inbox: queue.Queue = queue.Queue()
        self._stopped = threading.Event()
        self._thread: threading.Thread | None = None

        # CheckQuorum leader lease (reference raft.go:237 CheckQuorum):
        # a leader that hears from fewer than a quorum of peers within one
        # election timeout steps down instead of accepting work while
        # partitioned. _recent_active records peers that responded since
        # the last lease checkpoint.
        self.check_quorum = True
        self._quorum_elapsed = 0
        self._recent_active: set[int] = set()

        # Read lease (ISSUE 13; Raft dissertation §6.4 lease/ReadIndex):
        # the leader piggybacks `lease_duration` seconds of read lease +
        # its commit index on every AppendEntries; a follower may serve
        # BOUNDED-STALENESS reads (snapshot no older than that commit
        # index) while its discounted lease is live. Soundness rides two
        # legs: the CheckQuorum vote-withholding half (followers that
        # heard this leader within election_tick ignore campaigns — the
        # operator, i.e. the daemon wiring, must keep lease_duration
        # BELOW election_tick × tick_interval), and QUORUM-ANCHORED
        # granting (_lease_ttl: grants shrink as the leader's last
        # observed quorum contact ages, so a minority-partitioned
        # leader stops extending leases at once instead of until its
        # CheckQuorum step-down). 0.0 disables granting entirely.
        # Leader-side state is worker-thread-only; the follower-side
        # triple below is written by the worker and read lock-free by
        # RPC threads (plain floats/ints under the GIL). The _on_append
        # grant site orders the writes — deadline zeroed first on a term
        # change, written last on a grant, after the index — so a torn
        # read can only look like an expired lease or an over-strict
        # index, never a live lease gating on a stale index.
        from ..utils.clock import REAL_CLOCK

        self.lease_duration = lease_duration
        self.clock = clock or REAL_CLOCK
        self._read_lease_until = 0.0     # local monotonic deadline
        self._read_lease_index = 0       # leader commit at grant
        self._read_lease_term = -1       # grants die with their term
        # leader-side grant anchor: the last time THIS leader observed
        # responses from a quorum (see _lease_ttl — grants SHRINK as
        # quorum contact ages, so a partitioned leader stops extending
        # leases long before its CheckQuorum step-down fires)
        self._lease_quorum_contact = 0.0
        self._lease_acked: set[int] = set()

        # PreVote (raft §9.6 / etcd PreVote): an election-timeout node
        # first polls peers with a NON-disruptive pre-vote at term+1 —
        # only a pre-quorum starts a real campaign and bumps the term.
        # The reference leaves etcd's PreVote off and eats the inflated-
        # term disruption when a starved/partitioned node wakes up; under
        # CPU-starved hosts that wake-up churns elections, so this build
        # turns it on (deliberate robustness divergence). Leadership
        # transfers skip straight to a real campaign (etcd
        # campaignTransfer).
        self.pre_vote = True
        self._pre_votes: set[int] | None = None

        # streamed-snapshot pause state: peer -> _SnapPending; while set,
        # data appends to that peer are withheld (heartbeats still flow)
        # and stale failure hints ignored (etcd ProgressStateSnapshot
        # analogue). The resend deadline is CLOCK-based so chunk-loss
        # schedules replay deterministically under a FakeClock.
        self.snapshot_resend_seconds = snapshot_resend_seconds
        self.snap_stream_max_bytes = snap_stream_max_bytes
        self._snap_pending: dict[int, _SnapPending] = {}
        # follower-side chunk reassembly: (frm, snapshot_index) -> {seq: bytes}
        self._snap_chunks: dict[tuple[int, int], dict[int, bytes]] = {}
        # highest CONTIGUOUS seq held per reassembly buffer — what the
        # follower acks; pruned in lockstep with _snap_chunks
        self._snap_contig: dict[tuple[int, int], int] = {}
        # recovery-plane observability (worker-thread ints; status() and
        # the debugserver expose them, tests assert on them exactly)
        self.snap_chunks_sent = 0
        self.snap_chunks_resent = 0
        self.snap_resume_suffix = 0
        self.snap_chunks_rejected = 0
        self.snap_installs = 0
        self.snap_install_seconds = 0.0
        # per-peer count of unacked append messages — the pipelining
        # window; reset on rewind, decremented per response
        self._inflight: dict[int, int] = {}

        self.transfer_min_ticks = TRANSFER_MIN_TICKS
        self._transfer_cooldown = 0
        # leader-side cache of the serialized snapshot blob: re-streams of
        # the same snapshot_index must be byte-identical, or a follower
        # reassembling across two streams installs a state no leader had
        self._snap_blob: tuple[int, bytes] | None = None

        # Signalled leadership (reference raft.go signalledLeadership +
        # :644-670 ordering): election alone does not make a usable leader —
        # the new term's no-op barrier entry must commit AND every earlier
        # -term entry must be applied first. Only then is leadership
        # announced and proposals accepted. Without this, leader-side
        # components start writing (taking the store update lock) while this
        # worker thread still needs that lock to apply the previous
        # leader's tail entries — a deadlock until the propose timeout.
        self._signalled = False
        self._barrier_index = 0

        # ---- batched Ready plane (group commit) ----
        # entries appended since the last flush, persisted in ONE
        # append_entries call (one WAL write + one fsync for the batch)
        self._ready_entries: list[Entry] = []
        # term/vote/commit changed since the last flush (dirty-gated
        # save_hard_state, at most one per flush)
        self._hs_dirty = False
        # outgoing messages buffered until AFTER the flush persisted
        # entries + hard state: nothing leaves this node before the state
        # it claims is durable (votes/term bumps persist before any
        # message leaves — the raft durability contract)
        self._out_msgs: list = []
        # peers owed an AppendEntries this flush: peer -> allow_empty
        # (True once any requester allowed a heartbeat); coalesced to ONE
        # send_append per peer per flush
        self._append_dirty: dict[int, bool] = {}
        # flush observability (worker-thread ints; status() exposes them)
        self.ready_flushes = 0
        self.ready_items = 0
        self.commits_applied = 0

        # read-only degradation (ISSUE 3): an ENOSPC on the WAL demotes
        # this node to a follower that keeps serving reads/heartbeats
        # but REJECTS proposals, instead of crash-looping the worker.
        # A periodic storage probe (election_tick cadence) lifts the
        # degradation once the disk accepts durable writes again.
        self.storage_degraded = False
        self._degraded_elapsed = 0
        self.storage_errors = 0

        self._recovered = False
        if auto_recover:
            self.recover()

    def recover(self):
        """Replay persisted state (WAL + snapshot). Callers that swap in
        apply_entry/restore_state after construction (e.g. RaftProposer)
        pass auto_recover=False and invoke this once wiring is complete —
        otherwise recovered entries would be applied into a void."""
        if self._recovered:
            return
        self._recovered = True
        if self.storage is not None:
            self._restore_from_storage()
            self._flush_ready()   # replay marked hardstate dirty; settle it

    # ------------------------------------------------------------- lifecycle
    def start(self):
        self.recover()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"raft-{self.id}")
        self._thread.start()

    def stop(self):
        self._stopped.set()
        self._inbox.put(("stop",))
        if self._thread:
            self._thread.join(timeout=5)
        was_leader = self.role == LEADER
        self.role = FOLLOWER
        if was_leader:
            self._notify_leadership(False)

    def bootstrap(self, peers: list[Peer]):
        """Initialize a fresh cluster membership (first node, or test rig)."""
        for p in peers:
            self.members[p.raft_id] = p
        if self.storage is not None:
            self.storage.save_membership(self.members, self.removed_ids)

    # -------------------------------------------------------------- external
    def step(self, msg):
        """Feed a network message (thread-safe)."""
        self._inbox.put(("msg", msg))

    def tick(self):
        self._inbox.put(("tick",))

    def propose(self, data: Any, request_id: str,
                callback: Callable[[bool, str], None],
                trace_ctx=None):
        """Propose a normal entry; callback(ok, err) fires on commit (from
        the worker thread) or on drop. `trace_ctx` (optional) is the
        proposer's span context — it rides the staged Entry so the WAL
        fsync / commit / apply spans on every replica join the
        proposal's trace (utils/trace.py)."""
        self._inbox.put(("propose", data, request_id, callback, trace_ctx))

    def propose_conf_change(self, cc: ConfChange, request_id: str,
                            callback: Callable[[bool, str], None]):
        self._inbox.put(("conf", cc, request_id, callback))

    def transfer_leadership(self):
        """Hand leadership to the most caught-up peer (wedged-store escape
        hatch, raft.go:589-606): send it TimeoutNow so it campaigns at once;
        its higher term deposes us. No-op unless we lead with peers."""
        self._inbox.put(("transfer",))

    def notify_removed(self):
        """The transport learned from a peer that WE were removed from
        the membership (the peer's removed-member reply) — e.g. this
        member was demoted while down and restarted with a stale
        membership. Thread-safe."""
        self._inbox.put(("removed",))

    def campaign(self):
        """Force an immediate election (tests / bootstrap)."""
        self._inbox.put(("campaign",))

    # -------------------------------------------------- node-id membership
    # (reference: manager/state/raft/membership/cluster.go keeps the
    # raft-id ↔ node-id registry; role manager addresses members by node id)

    def member_by_node_id(self, node_id: str) -> Peer | None:
        members = self.members  # snapshot: membership is copy-on-write
        for p in members.values():
            if p.node_id == node_id:
                return p
        return None

    def is_member(self, node_id: str) -> bool:
        return self.member_by_node_id(node_id) is not None

    def can_remove_member(self, node_id: str) -> bool:
        members = self.members  # snapshot: membership is copy-on-write
        peer = None
        for p in members.values():
            if p.node_id == node_id:
                peer = p
                break
        if peer is None:
            return True  # nothing to remove
        remaining = [p for p in members if p != peer.raft_id]
        if not remaining:
            return False
        reachable = sum(
            1 for p in remaining if p == self.id or self.transport.active(p)
        )
        return reachable >= len(remaining) // 2 + 1

    def remove_member_by_node_id(self, node_id: str, timeout: float = 10.0) -> bool:
        """Propose removal of the member with this node id, blocking until
        the conf change commits (reference raft.go Leave/RemoveMember)."""
        peer = self.member_by_node_id(node_id)
        if peer is None:
            return True
        done = threading.Event()
        result: dict[str, Any] = {}

        def cb(ok, err=""):
            result["ok"] = ok
            result["err"] = err
            done.set()

        from ..utils.identity import new_id as _new_id

        self.propose_conf_change(
            ConfChange(action="remove", raft_id=peer.raft_id, node_id=node_id),
            _new_id(),
            cb,
        )
        done.wait(timeout)
        return bool(result.get("ok"))

    # ------------------------------------------------------------ event loop
    def _run(self):
        """Batched Ready loop: drain the inbox (bounded batch), dispatch
        every item, then perform ONE flush for the whole batch — the
        group-commit plane. Handlers only mutate volatile state and mark
        work (entries to persist, peers to append to, messages to send);
        `_flush_ready` is the single point where durability and the
        network happen."""
        while not self._stopped.is_set():
            try:
                item = self._inbox.get(timeout=0.2)
            except queue.Empty:
                continue
            batch = [item]
            while len(batch) < MAX_READY_BATCH:
                try:
                    batch.append(self._inbox.get_nowait())
                except queue.Empty:
                    break
            for it in batch:
                try:
                    self._dispatch(it)
                except Exception:
                    log.exception("raft-%d: error processing %r",
                                  self.id, it[0])
            try:
                self._flush_ready()
            except Exception:
                # unsent messages may claim durability the failed flush
                # never provided — drop them; raft retransmits
                self._out_msgs.clear()
                log.exception("raft-%d: ready flush failed", self.id)

    def process_all(self):
        """Drain the inbox synchronously (fake-clock tests drive this):
        the same dispatch-all-then-flush-once shape as the live worker."""
        processed = False
        while True:
            try:
                item = self._inbox.get_nowait()
            except queue.Empty:
                break
            processed = True
            self._dispatch(item)
        # flush pending ready state even when the inbox was empty: tests
        # drive handlers directly (e.g. _on_transfer) and their buffered
        # output must still reach the wire
        if processed or self._out_msgs or self._ready_entries \
                or self._hs_dirty or self._append_dirty:
            self._flush_ready()

    def _flush_ready(self):
        """The group-commit flush (etcd Ready/Advance analogue), in strict
        order: (1) persist the batch's entries — one WAL append, one
        fsync; (2) advance the commit frontier off the durable state and
        apply, firing wait callbacks in log order; (3) dirty-gated
        hardstate save — votes/term bumps/commit persist here, BEFORE any
        message leaves; (4) one coalesced AppendEntries per dirty peer;
        (5) release the buffered outgoing messages to the transport."""
        self.ready_flushes += 1
        # ready-loop trace guard: ONE module-global truthiness test when
        # disarmed (the bench/test acceptance)
        if not trace.enabled():
            self._flush_ready_stages(None)
            return
        # one raft.ready span per flush, parented to the batch's first
        # traced entry so the flush joins that proposal's causal trace
        entries = self._ready_entries
        with trace.span("raft.ready",
                        parent=next((e.trace for e in entries
                                     if e.trace is not None), None),
                        node=self.id, entries=len(entries)) as sp:
            self._flush_ready_stages(sp)

    def _flush_ready_stages(self, sp):
        """_flush_ready's stages; sp is its raft.ready span, None when
        tracing is disarmed."""
        if self._ready_entries:
            if self.storage is not None:
                if sp is not None:
                    _t0 = time.perf_counter()
                    _n = len(self._ready_entries)
                try:
                    self.storage.append_entries(self._ready_entries)
                except OSError as exc:
                    self._on_append_failure(exc)
                    return
                if sp is not None:
                    # one span per GROUP append (one WAL write + fsync),
                    # never per-entry
                    trace.rec("raft.wal_fsync",
                              time.perf_counter() - _t0, parent=sp,
                              node=self.id, entries=_n)
                if self.storage_degraded:
                    # the disk took a durable batch again: leave
                    # read-only mode (the follower catch-up path heals
                    # without waiting for the tick-driven probe)
                    self.storage_degraded = False
                    log.info("raft-%d: WAL writable again; leaving "
                             "read-only degradation", self.id)
            self._ready_entries = []
        self._maybe_advance_commit()
        self._apply_committed()
        if self._hs_dirty:
            if self.storage is not None:
                try:
                    self.storage.save_hard_state(self.term, self.voted_for,
                                                 self.commit_index)
                except OSError as exc:
                    # votes/term bumps are NOT durable: nothing buffered
                    # may leave (a granted vote without a persisted
                    # voted_for can elect two leaders across a restart)
                    self.storage_errors += 1
                    self._out_msgs.clear()
                    self._append_dirty.clear()
                    self._maybe_degrade(exc)
                    log.warning("raft-%d: hardstate save failed (%s); "
                                "holding %s", self.id, exc,
                                "read-only" if self.storage_degraded
                                else "retry")
                    return
            # cleared only AFTER a successful save (like _ready_entries):
            # a failed write must leave the flag set so the next flush
            # retries before any message claims the state is durable
            self._hs_dirty = False
        if self._append_dirty:
            dirty, self._append_dirty = self._append_dirty, {}
            if self.role == LEADER:
                for peer_id, allow_empty in dirty.items():
                    if peer_id in self.members and peer_id != self.id:
                        self._send_append_to(peer_id,
                                             allow_empty=allow_empty)
        if self._out_msgs:
            msgs, self._out_msgs = self._out_msgs, []
            for m in msgs:
                try:
                    self.transport.send(m)
                except Exception:
                    log.debug("raft-%d: send to %d failed", self.id, m.to)

    def _on_append_failure(self, exc: OSError):
        """A group append failed. The storage rolled the batch back, so
        the volatile state must follow: the batch ATOMICALLY never
        happened. Staged entries leave the in-memory log, every staged
        proposal's wait callback fires with the error (no proposal may
        hang forever on a dropped batch), and nothing buffered reaches
        the network — the messages claim durability the flush never
        provided. A leader steps down (it cannot persist its own log);
        ENOSPC additionally degrades the node to a read-only follower
        that keeps serving reads/heartbeats but rejects proposals."""
        self.storage_errors += 1
        batch, self._ready_entries = self._ready_entries, []
        keep = batch[0].index - self.first_index
        if keep >= 0:
            self.log = self.log[:keep]
        self.commit_index = max(self.last_applied,
                                min(self.commit_index, self._last_index()))
        err = f"raft storage append failed: {exc}"
        for e in batch:
            cb = self._waits.pop(e.request_id, None) if e.request_id else None
            if cb is not None:
                try:
                    cb(False, err)
                except Exception:
                    log.exception("raft-%d: wait callback failed", self.id)
        self._out_msgs.clear()
        self._append_dirty.clear()
        log.warning("raft-%d: WAL append of %d entries failed: %s",
                    self.id, len(batch), exc)
        self._maybe_degrade(exc)
        if self.role == LEADER:
            # a leader that cannot persist its log must not keep
            # accepting work; let a disk-healthy peer take over
            self._become_follower(self.term, None)

    def _maybe_degrade(self, exc):
        import errno as _errno

        # a WEDGED storage (failed batch whose rollback also failed)
        # must degrade too: probe() is the only un-wedge path and it
        # only runs from the degradation loop
        wedged = self.storage is not None \
            and getattr(self.storage, "_wedged", False)
        if getattr(exc, "errno", None) != _errno.ENOSPC and not wedged:
            return
        if not self.storage_degraded:
            self.storage_degraded = True
            self._degraded_elapsed = 0
            log.warning("raft-%d: WAL %s; degrading to read-only "
                        "follower", self.id,
                        "wedged" if wedged else "out of space")
        if self.role == LEADER:
            self._become_follower(self.term, None)

    def _dispatch(self, item):
        self.ready_items += 1
        kind = item[0]
        if kind == "msg":
            self._step(item[1])
        elif kind == "tick":
            self._on_tick()
        elif kind == "propose":
            self._on_propose(item[1], item[2], item[3],
                             item[4] if len(item) > 4 else None)
        elif kind == "conf":
            self._on_conf_change(item[1], item[2], item[3])
        elif kind == "campaign":
            self._campaign()
        elif kind == "transfer":
            self._on_transfer()
        elif kind == "removed":
            self._handle_self_removed()

    # ----------------------------------------------------------------- ticks
    def _next_timeout(self) -> int:
        return self.election_tick + self._rng.randrange(self.election_tick)

    def _on_tick(self):
        if self._transfer_cooldown > 0:
            self._transfer_cooldown -= 1
        if self.storage_degraded:
            # read-only degradation: probe the disk at election_tick
            # cadence; a writable disk lifts the degradation (the
            # follower append path also lifts it on its first durable
            # batch)
            self._degraded_elapsed += 1
            if self._degraded_elapsed >= self.election_tick:
                self._degraded_elapsed = 0
                if self.storage is not None and self.storage.probe():
                    self.storage_degraded = False
                    log.info("raft-%d: storage probe succeeded; leaving "
                             "read-only degradation", self.id)
        if self.role == LEADER:
            self.heartbeat_elapsed += 1
            if self.heartbeat_elapsed >= self.heartbeat_tick:
                self.heartbeat_elapsed = 0
                self._mark_broadcast()
            # expire paused streamed snapshots so lost chunks get
            # re-sent — clock-deadline based (FakeClock-deterministic),
            # and a resume re-sends ONLY the suffix past the follower's
            # acked contiguous prefix, never the whole blob
            if self._snap_pending:
                now = self.clock.monotonic()
                for peer_id, pending in list(self._snap_pending.items()):
                    if now >= pending.deadline:
                        self._resend_snapshot_suffix(peer_id, pending, now)
            if self.check_quorum:
                self._quorum_elapsed += 1
                if self._quorum_elapsed >= self.election_tick:
                    self._quorum_elapsed = 0
                    active = {self.id} | (
                        self._recent_active & set(self.members))
                    self._recent_active = set()
                    if not self._quorum(len(active)):
                        # partitioned leader: step down rather than keep
                        # accepting work a real quorum will supersede
                        # (reference raft.go CheckQuorum behavior)
                        log.info(
                            "raft-%d: leader lost quorum contact "
                            "(%d/%d active); stepping down",
                            self.id, len(active), len(self.members))
                        self._become_follower(self.term, None)
        else:
            self.election_elapsed += 1
            if self.election_elapsed >= self._randomized_timeout:
                self._campaign()

    # -------------------------------------------------------------- election
    def _campaign(self, transfer: bool = False):
        if self.id not in self.members:
            # removed members must not start elections, and a freshly joined
            # node that has not yet learned the membership (empty config)
            # must not self-elect as a quorum-of-one
            return
        if self.pre_vote and not transfer:
            # poll first; only a pre-quorum bumps the term (_real_campaign)
            self._pre_campaign()
            return
        self._real_campaign(transfer=transfer)

    def _enter_candidacy(self):
        self.role = CANDIDATE
        self.leader_id = None
        self.election_elapsed = 0
        self._randomized_timeout = self._next_timeout()
        # stale real votes from a PRIOR campaign at this term must not
        # survive into a pre-campaign: a delayed VoteResponse grant
        # passes the non-pre vote_resp gate (role==CANDIDATE, term
        # match) and could elect a pre-candidate without any pre-quorum
        # — leadership is only reachable via _real_campaign, which
        # re-seeds votes with the self-vote (ADVICE r5)
        self.votes = set()

    def _pre_campaign(self):
        self._enter_candidacy()
        # NO term bump, NO voted_for, NO persist — a pre-candidate that
        # cannot reach a quorum leaves no trace (raft §9.6)
        self._pre_votes = {self.id}
        if self._quorum(len(self._pre_votes)):
            self._real_campaign()
            return
        for peer_id in self.members:
            if peer_id == self.id:
                continue
            self._send(VoteRequest(
                frm=self.id, to=peer_id, term=self.term + 1,
                last_log_index=self._last_index(),
                last_log_term=self._last_term(),
                pre=True,
            ))

    def _real_campaign(self, transfer: bool = False):
        self._pre_votes = None
        self._enter_candidacy()
        self.term += 1
        self.voted_for = self.id
        self.votes = {self.id}
        self._persist_hard_state()
        if self._quorum(len(self.votes)):
            self._become_leader()
            return
        for peer_id in self.members:
            if peer_id == self.id:
                continue
            self._send(VoteRequest(
                frm=self.id, to=peer_id, term=self.term,
                last_log_index=self._last_index(),
                last_log_term=self._last_term(),
                transfer=transfer,
            ))

    def _quorum(self, n: int) -> bool:
        voters = len(self.members) or 1
        return n >= voters // 2 + 1

    def _become_leader(self):
        self._pre_votes = None
        self.role = LEADER
        self.leader_id = self.id
        self.heartbeat_elapsed = 0
        self._quorum_elapsed = 0
        self._recent_active = set()
        # a quorum just voted for us: that IS quorum contact
        self._lease_quorum_contact = self.clock.monotonic()
        self._lease_acked = set()
        self._snap_pending = {}
        self._inflight = {}
        last = self._last_index()
        self.next_index = {p: last + 1 for p in self.members if p != self.id}
        self.match_index = {p: 0 for p in self.members if p != self.id}
        # commit a no-op entry from the new term so earlier-term entries can
        # commit (raft §5.4.2 safety rule); leadership is signalled only
        # once this barrier applies (_apply_committed)
        self._signalled = False
        self._barrier_index = last + 1
        self._append_local(Entry(term=self.term, index=last + 1,
                                 kind=ENTRY_NORMAL, data=None))
        self._mark_broadcast()
        # commit advance (single-node clusters commit the barrier at once)
        # happens at this batch's flush, after the entry is durable

    def _become_follower(self, term: int, leader_id: int | None):
        was_leader = self.role == LEADER
        was_signalled = self._signalled
        self._signalled = False
        self._pre_votes = None
        if term > self.term:
            self.term = term
            self.voted_for = None
            self._persist_hard_state()
            # a partial snapshot stream from a deposed leader is dead;
            # drop its reassembly buffers (and their ack watermarks)
            self._snap_chunks.clear()
            self._snap_contig.clear()
        self.role = FOLLOWER
        self.leader_id = leader_id
        self.election_elapsed = 0
        self._randomized_timeout = self._next_timeout()
        if was_leader:
            self._drop_waits(ERR_LEADERSHIP_LOST)
            if was_signalled:
                self._notify_leadership(False)

    def _notify_leadership(self, is_leader: bool):
        try:
            self.on_leadership(is_leader)
        except Exception:
            log.exception("raft-%d: leadership callback failed", self.id)

    # ------------------------------------------------------------------ step
    def _step(self, msg):
        if (msg.kind == "vote_req" and self.check_quorum
                and not getattr(msg, "transfer", False)
                and self.leader_id is not None
                and self.election_elapsed < self.election_tick):
            # Leader lease (the vote-withholding half of etcd CheckQuorum,
            # which the reference gets from raft.Config CheckQuorum=true —
            # manager/state/raft/raft.go:492): a node that heard from a
            # live leader within the minimum election timeout IGNORES
            # disruptive campaigns entirely — no term bump, no response.
            # Without this, one starved/partition-returned node waking up
            # with an inflated term deposes a healthy leader and churns
            # elections under load. Applies to pre-votes and real votes
            # alike; leadership transfers bypass the lease via the
            # transfer flag (etcd campaignTransfer).
            return
        if msg.term > self.term:
            if msg.kind == "vote_req" and getattr(msg, "pre", False):
                # a pre-vote poll at a PROSPECTIVE term changes no state
                # here; _on_vote_request answers it without granting a
                # real vote (etcd: "Never change our term in response to
                # a PreVote")
                pass
            elif msg.kind == "vote_resp" and getattr(msg, "pre", False) \
                    and msg.granted:
                # a granted pre-vote echoes OUR prospective term back;
                # adopting it would double-bump the real campaign's term
                pass
            else:
                self._become_follower(msg.term, getattr(msg, "frm", None)
                                      if msg.kind == "append" else None)
        handler = {
            "vote_req": self._on_vote_request,
            "vote_resp": self._on_vote_response,
            "append": self._on_append,
            "append_resp": self._on_append_response,
            "snapshot": self._on_install_snapshot,
            "snap_chunk": self._on_snapshot_chunk,
            "snap_ack": self._on_snapshot_ack,
            "timeout_now": self._on_timeout_now,
        }.get(msg.kind)
        if handler:
            handler(msg)

    def _on_timeout_now(self, msg):
        """Leadership-transfer target: campaign immediately (raft §3.10).
        Gated on the CURRENT term's leader — a delayed/replayed transfer
        from a deposed leader must not disrupt a healthy one (etcd gates
        MsgTimeoutNow the same way)."""
        if self.id in self.members and msg.term == self.term \
                and msg.frm == self.leader_id:
            self._campaign(transfer=True)

    def _on_transfer(self):
        from .messages import TimeoutNow

        if self.role != LEADER:
            return
        # rate limit (reference raft.go:569-604: 1/min): the wedge monitor
        # may fire repeatedly while the store stays stuck, and back-to-back
        # transfers churn elections instead of letting the new leader
        # settle. Tick-counted so the fake-clock harness can drive expiry.
        if self._transfer_cooldown > 0:
            log.info("raft-%d: leadership transfer suppressed (rate limit)",
                     self.id)
            return
        peers = [p for p in self.members if p != self.id]
        if not peers:
            return
        self._transfer_cooldown = self.transfer_min_ticks
        target = max(peers, key=lambda p: self.match_index.get(p, 0))
        self._send(TimeoutNow(frm=self.id, to=target, term=self.term))

    def _on_vote_request(self, msg: VoteRequest):
        up_to_date = (msg.last_log_term, msg.last_log_index) >= (
            self._last_term(), self._last_index())
        if getattr(msg, "pre", False):
            # pre-vote: would we vote for this log at that future term?
            # Granting records NOTHING (no voted_for, no timer reset) —
            # many nodes may grant the same pre-term to different
            # pre-candidates; only real votes are exclusive
            grant = msg.term > self.term and up_to_date
            self._send(VoteResponse(
                frm=self.id, to=msg.frm,
                term=msg.term if grant else self.term,
                granted=grant, pre=True))
            return
        grant = False
        if msg.term >= self.term:
            not_voted = self.voted_for in (None, msg.frm)
            if up_to_date and not_voted and msg.term == self.term:
                grant = True
                self.voted_for = msg.frm
                self.election_elapsed = 0
                self._persist_hard_state()
        self._send(VoteResponse(frm=self.id, to=msg.frm, term=self.term,
                                granted=grant))

    def _on_vote_response(self, msg: VoteResponse):
        if getattr(msg, "pre", False):
            if (self.role != CANDIDATE or self._pre_votes is None
                    or not msg.granted or msg.term != self.term + 1):
                # rejections with a HIGHER real term already demoted us in
                # _step; stale or duplicate grants are ignored
                return
            self._pre_votes.add(msg.frm)
            if self._quorum(len(self._pre_votes)):
                self._real_campaign()
            return
        if self.role != CANDIDATE or msg.term != self.term:
            return
        if msg.granted:
            self.votes.add(msg.frm)
            if self._quorum(len(self.votes)):
                self._become_leader()

    def _on_append(self, msg: AppendEntries):
        if msg.term < self.term:
            self._send(AppendResponse(frm=self.id, to=msg.frm, term=self.term,
                                      success=False, match_index=0))
            return
        self.role = FOLLOWER
        self.leader_id = msg.frm
        self.election_elapsed = 0

        if getattr(msg, "lease_ttl", 0.0) > 0.0:
            # read-lease grant from the current-term leader: the
            # follower trusts it only DISCOUNTED by the skew margin, and
            # grants never shrink an existing deadline (out-of-order
            # delivery). A term change invalidates the previous term's
            # grants wholesale — a deposed leader's lease must not let
            # this follower serve past the new leader's writes for
            # longer than the old leader's own guarantee window.
            # WRITE ORDER is load-bearing for lock-free RPC readers
            # (read_ok): the deadline is zeroed FIRST on a term change
            # and written LAST on a grant, AFTER the index it gates —
            # a torn read can only look like an expired lease or an
            # over-strict index, never a live lease with a stale index.
            if self._read_lease_term != self.term:
                self._read_lease_until = 0.0
                self._read_lease_index = 0
                self._read_lease_term = self.term
            self._read_lease_index = max(self._read_lease_index,
                                         msg.leader_commit)
            self._read_lease_until = max(
                self._read_lease_until,
                self.clock.monotonic()
                + msg.lease_ttl * (1.0 - READ_LEASE_SKEW))

        # prev entry check
        if msg.prev_log_index > 0:
            if msg.prev_log_index < self.snapshot_index:
                # already compacted; our snapshot covers it
                pass
            elif msg.prev_log_index > self._last_index() or (
                    self._term_at(msg.prev_log_index) != msg.prev_log_term):
                self._send(AppendResponse(
                    frm=self.id, to=msg.frm, term=self.term, success=False,
                    match_index=min(self._last_index(), msg.prev_log_index - 1)))
                return

        for e in msg.entries:
            if e.index <= self.snapshot_index:
                continue
            if e.index <= self._last_index():
                if self._term_at(e.index) != e.term:
                    # conflict: truncate from here
                    self.log = self.log[: e.index - self.first_index]
                    self._append_entry_storage_truncate(e.index)
                    self.log.append(e)
                    self._persist_entry(e)
            else:
                self.log.append(e)
                self._persist_entry(e)

        if msg.leader_commit > self.commit_index:
            # the apply (and the hardstate save recording the advance)
            # happens at this batch's flush, AFTER the entries above are
            # durably appended
            self.commit_index = min(msg.leader_commit, self._last_index())

        if self._snap_chunks:
            # appends caught us up past a partially-streamed snapshot
            # (its sender died mid-stream): the buffers are garbage now
            self._prune_snap_buffers(self._last_index())

        self._send(AppendResponse(frm=self.id, to=msg.frm, term=self.term,
                                  success=True,
                                  match_index=self._last_index()))

    def _on_append_response(self, msg: AppendResponse):
        if self.role != LEADER or msg.term != self.term:
            return
        self._recent_active.add(msg.frm)  # CheckQuorum lease contact
        # read-lease anchor: once responses from a quorum accumulate,
        # re-anchor the grant window and start collecting afresh (the
        # set is reset on every quorum so the anchor tracks ROUNDS of
        # quorum contact, not a window that one chatty peer keeps warm)
        self._lease_acked.add(msg.frm)
        if self._quorum(len(self._lease_acked | {self.id})):
            self._lease_quorum_contact = self.clock.monotonic()
            self._lease_acked.clear()
        if msg.success:
            # one ack drains one window slot (heartbeat acks merely decay
            # the counter faster, floored at zero)
            self._inflight[msg.frm] = max(
                0, self._inflight.get(msg.frm, 0) - 1)
            self.match_index[msg.frm] = max(
                self.match_index.get(msg.frm, 0), msg.match_index)
            # pipelined sends advanced next_index optimistically past
            # match+1 — never regress it on an (out-of-order) ack
            self.next_index[msg.frm] = max(
                self.next_index.get(msg.frm, 1),
                self.match_index[msg.frm] + 1)
            pending = self._snap_pending.get(msg.frm)
            if pending is not None and msg.match_index >= pending.snap_idx:
                self._snap_pending.pop(msg.frm, None)  # install acked
            # commit advance runs once at the flush, over the whole
            # batch of acks; refill the pipeline window opened by this
            # ack with ONE coalesced append per peer per flush
            self._mark_append(msg.frm, allow_empty=False)
        else:
            if msg.frm in self._snap_pending:
                # mid-install heartbeat mismatch is expected; the streamed
                # snapshot (or its TTL expiry) resolves it
                return
            # follower hinted how far behind it is; with a pipeline in
            # flight, stale rejections of already-superseded probes carry
            # hints >= next — only a genuinely lower hint rewinds
            self._inflight[msg.frm] = 0  # everything in flight is moot
            new_next = max(1, msg.match_index + 1)
            if new_next < self.next_index.get(msg.frm,
                                              self._last_index() + 1):
                self.next_index[msg.frm] = new_next
                self._mark_append(msg.frm, allow_empty=False)

    def _on_install_snapshot(self, msg: InstallSnapshot):
        if msg.term < self.term:
            return
        self.role = FOLLOWER
        self.leader_id = msg.frm
        self.election_elapsed = 0
        self._install_snapshot(msg.frm, msg.snapshot_index,
                               msg.snapshot_term, msg.members, msg.data,
                               removed=msg.removed)

    def _on_snapshot_chunk(self, msg):
        """Reassemble a streamed snapshot; apply when complete. Every chunk
        counts as leader contact (the follower must not campaign while a
        multi-second install is in flight). Resumable (ISSUE 18): chunks
        are byte-identical per snapshot_index (leader-side _snap_blob
        cache), so the buffer is filled idempotently — dup/reorder are
        no-ops, a suffix resend fills holes without losing the prefix —
        and every chunk is answered with a SnapshotAck carrying the
        highest CONTIGUOUS seq held."""
        if msg.term < self.term:
            return
        if failpoints.fp_value("raft.snap.chunk_drop", False):
            # injected chunk loss (docs/fault_injection.md): the chunk
            # never existed as far as this follower is concerned
            return
        self.role = FOLLOWER
        self.leader_id = msg.frm
        self.election_elapsed = 0
        if msg.snapshot_index <= self.snapshot_index:
            # already have it (dup/late chunks): ack so the leader unpauses
            self._send(AppendResponse(
                frm=self.id, to=msg.frm, term=self.term, success=True,
                match_index=self._last_index()))
            return
        if (msg.total <= 0 or not 0 <= msg.seq < msg.total
                or len(msg.chunk) > SNAPSHOT_CHUNK_BYTES
                or msg.total * SNAPSHOT_CHUNK_BYTES
                > self.snap_stream_max_bytes):
            # reassembly cap / malformed framing: a buggy or deposed
            # leader must not balloon this follower's memory
            self.snap_chunks_rejected += 1
            _snap_events.inc(("chunk_rejected",))
            return
        key = (msg.frm, msg.snapshot_index)
        if key not in self._snap_chunks:
            for k in [k for k in self._snap_chunks if k[0] == msg.frm]:
                if k[1] > msg.snapshot_index:
                    # a late chunk of a stream this sender already
                    # abandoned for a newer snapshot: ignore it
                    return
                # eager orphan eviction: at most ONE live buffer per
                # sender — the newer stream supersedes the older one
                self._snap_chunks.pop(k, None)
                self._snap_contig.pop(k, None)
        buf = self._snap_chunks.setdefault(key, {})
        buf[msg.seq] = msg.chunk
        c = self._snap_contig.get(key, -1)
        while c + 1 in buf:
            c += 1
        self._snap_contig[key] = c
        # progress report: the leader re-arms its resend deadline on
        # advance and, on expiry, re-sends only chunks past `acked`
        self._send(SnapshotAck(
            frm=self.id, to=msg.frm, term=self.term,
            snapshot_index=msg.snapshot_index, acked=c))
        if len(buf) < msg.total:
            return
        from ..rpc import codec

        data = codec.loads(b"".join(buf[i] for i in range(msg.total)))
        # drop every reassembly buffer for this or older snapshots
        self._prune_snap_buffers(msg.snapshot_index)
        self._install_snapshot(msg.frm, msg.snapshot_index,
                               msg.snapshot_term, msg.members, data,
                               removed=msg.removed)

    def _on_snapshot_ack(self, msg):
        """Leader side of the resumable stream: record the follower's
        contiguous-prefix watermark and push the resend deadline out —
        a live, progressing stream is never re-blasted."""
        if self.role != LEADER or msg.term != self.term:
            return
        self._recent_active.add(msg.frm)  # CheckQuorum lease contact
        pending = self._snap_pending.get(msg.frm)
        if pending is None or pending.snap_idx != msg.snapshot_index:
            return
        if msg.acked > pending.acked:
            pending.acked = msg.acked
            pending.deadline = (self.clock.monotonic()
                                + self.snapshot_resend_seconds)

    def _resend_snapshot_suffix(self, peer_id: int, pending: _SnapPending,
                                now: float):
        """Resend deadline expired: re-send ONLY the chunks past the
        follower's acked contiguous prefix. If the snapshot advanced (or
        the blob cache no longer covers it) the stream is abandoned and
        the ordinary append path starts a fresh one."""
        if pending.snap_idx != self.snapshot_index \
                or self._snap_blob is None \
                or self._snap_blob[0] != pending.snap_idx:
            self._snap_pending.pop(peer_id, None)
            self._mark_append(peer_id, allow_empty=False)
            return
        blob = self._snap_blob[1]
        chunks = [blob[i:i + SNAPSHOT_CHUNK_BYTES]
                  for i in range(0, len(blob), SNAPSHOT_CHUNK_BYTES)] or [b""]
        # the min(..., total-1) floor guarantees at least one chunk goes
        # out even when every chunk was acked — that re-ack carries the
        # AppendResponse a lost install-ack deprived us of
        start = min(pending.acked + 1, len(chunks) - 1)
        members = {rid: (p.node_id, p.addr)
                   for rid, p in self.members.items()}
        removed = sorted(self.removed_ids)
        for seq in range(start, len(chunks)):
            self._send(SnapshotChunk(
                frm=self.id, to=peer_id, term=self.term,
                snapshot_index=pending.snap_idx,
                snapshot_term=self.snapshot_term,
                members=members, removed=removed,
                seq=seq, total=len(chunks), chunk=chunks[seq],
            ))
        resent = len(chunks) - start
        self.snap_chunks_resent += resent
        self.snap_resume_suffix += 1
        _snap_events.inc(("chunk_resent",), resent)
        _snap_events.inc(("suffix_resume",))
        pending.deadline = now + self.snapshot_resend_seconds

    def _prune_snap_buffers(self, upto_index: int):
        """Drop reassembly buffers (and their ack watermarks) for
        snapshots at or below `upto_index` — they are covered by state
        this node already holds."""
        self._snap_chunks = {
            k: v for k, v in self._snap_chunks.items() if k[1] > upto_index}
        self._snap_contig = {
            k: v for k, v in self._snap_contig.items() if k[1] > upto_index}

    def _install_snapshot(self, frm: int, snapshot_index: int,
                          snapshot_term: int, members, data, removed=()):
        if snapshot_index <= self.snapshot_index:
            return
        _t0 = time.perf_counter()
        self.snapshot_index = snapshot_index
        self.snapshot_term = snapshot_term
        self.log = []
        self.first_index = snapshot_index + 1
        # entries staged for this flush are covered (or superseded) by the
        # snapshot — and so is any divergent persisted tail BEYOND it,
        # which a later restart would otherwise splice after the snapshot
        # (the install replaced the whole log, the WAL must follow).
        # ORDER is crash-safety: the WAL truncate runs BEFORE the new
        # snapshot is saved, so a crash anywhere in the window leaves
        # old-snapshot + a (possibly truncated) consistent prefix — never
        # new-snapshot + a divergent old tail. The failpoint below sits
        # mid-window so tests can pin exactly that.
        self._ready_entries = [e for e in self._ready_entries
                               if e.index > snapshot_index]
        if self.storage is not None:
            self.storage.truncate_from(snapshot_index + 1)
        failpoints.fp("raft.snap.install")
        self.commit_index = max(self.commit_index, snapshot_index)
        self.last_applied = snapshot_index
        self.members = {
            rid: Peer(rid, nid, addr)
            for rid, (nid, addr) in members.items()
        }
        # merge, don't replace: removals this node saw that the leader's
        # snapshot predates must survive too
        self.removed_ids |= set(removed)
        self.restore_state(data)
        if self.storage is not None:
            self.storage.save_snapshot(
                snapshot_index, snapshot_term, data, self.members,
                removed=self.removed_ids)
            # keep membership.json in step: load() prefers it over the
            # snapshot's member list, so a stale file would resurrect a
            # pre-snapshot membership on restart
            self.storage.save_membership(self.members, self.removed_ids)
        self.snap_installs += 1
        self.snap_install_seconds += time.perf_counter() - _t0
        _snap_events.inc(("install",))
        self._send(AppendResponse(frm=self.id, to=frm, term=self.term,
                                  success=True, match_index=snapshot_index))

    # ------------------------------------------------------------- proposing
    def _on_propose(self, data, request_id, callback, trace_ctx=None):
        if self.storage_degraded:
            # read-only: reads/heartbeats keep flowing, writes bounce
            callback(False, "storage degraded (read-only): out of disk "
                            "space; proposal rejected")
            return
        if self.role != LEADER or not self._signalled:
            # an unsignalled leader has unapplied earlier-term entries;
            # accepting a proposal now deadlocks the applier against the
            # proposer's store lock (raft.go processInternalRaftRequest
            # fails on !signalledLeadership for the same reason)
            callback(False, f"{ERR_NOT_LEADER}; leader is {self.leader_id}")
            return
        self._waits[request_id] = callback
        e = Entry(term=self.term, index=self._last_index() + 1,
                  kind=ENTRY_NORMAL, data=data, request_id=request_id,
                  trace=trace_ctx)
        self._append_local(e)
        self._mark_broadcast()
        # the batch flush persists (one fsync for ALL proposals in the
        # batch), replicates (one coalesced append per peer) and advances
        # the commit (single-node clusters commit right at the flush)

    def _on_conf_change(self, cc: ConfChange, request_id, callback):
        if self.storage_degraded:
            callback(False, "storage degraded (read-only): out of disk "
                            "space; conf change rejected")
            return
        if self.role != LEADER or not self._signalled:
            callback(False, f"{ERR_NOT_LEADER}; leader is {self.leader_id}")
            return
        if cc.action == "remove" and not self._can_remove(cc.raft_id):
            callback(False, "removal would break quorum of reachable members")
            return
        self._waits[request_id] = callback
        e = Entry(term=self.term, index=self._last_index() + 1,
                  kind=ENTRY_CONF_CHANGE, data=cc, request_id=request_id)
        self._append_local(e)
        self._mark_broadcast()

    def _can_remove(self, raft_id: int) -> bool:
        """reference raft.go:1170-1193 CanRemoveMember: removal must leave a
        reachable quorum."""
        remaining = [p for p in self.members if p != raft_id]
        if not remaining:
            return False
        reachable = sum(
            1 for p in remaining
            if p == self.id or self.transport.active(p))
        return reachable >= len(remaining) // 2 + 1

    def _drop_waits(self, reason: str):
        waits, self._waits = self._waits, {}
        for cb in waits.values():
            try:
                cb(False, reason)
            except Exception:
                pass

    # ------------------------------------------------------------ replication
    def _append_local(self, e: Entry):
        self.log.append(e)
        self._persist_entry(e)
        if self.role == LEADER:
            self._maybe_snapshot()

    def _mark_append(self, peer_id: int, allow_empty: bool = True):
        """Note that `peer_id` is owed an AppendEntries; the batch flush
        coalesces every mark into ONE _send_append_to per peer."""
        self._append_dirty[peer_id] = (self._append_dirty.get(peer_id, False)
                                       or allow_empty)

    def _mark_broadcast(self):
        for peer_id in self.members:
            if peer_id != self.id:
                self._mark_append(peer_id)

    def _send_append_to(self, peer_id: int, allow_empty: bool = True):
        """Ship log entries to one peer, pipelined: batches are sent
        optimistically (next_index advances without waiting for acks) up
        to an in-flight window of MAX_INFLIGHT_APPENDS unacked messages,
        so catch-up throughput is window-bound instead of
        one-batch-per-RTT (reference MaxInflightMsgs). Before the first
        ack establishes `match`, the peer is in probe mode: one
        NON-advancing batch at a time (etcd ProgressStateProbe) — blasting
        optimistic batches at a possibly-mismatched log would bounce
        entirely."""
        next_idx = self.next_index.get(peer_id, self._last_index() + 1)
        if peer_id not in self._snap_pending and \
                next_idx <= self.snapshot_index:
            self._send_snapshot_to(peer_id)
            return
        lease_ttl = self._lease_ttl()
        match = self.match_index.get(peer_id, 0)
        paused = peer_id in self._snap_pending
        sent = 0
        while not paused:
            if self._inflight.get(peer_id, 0) >= MAX_INFLIGHT_APPENDS:
                break  # window full: heartbeat only until acks drain it
            next_idx = self.next_index.get(peer_id, self._last_index() + 1)
            start = next_idx - self.first_index
            entries = self.log[start:start + MAX_ENTRIES_PER_APPEND]
            if not entries:
                break
            prev_index = next_idx - 1
            prev_term = self._term_at(prev_index) if prev_index > 0 else 0
            self._send(AppendEntries(
                frm=self.id, to=peer_id, term=self.term,
                prev_log_index=prev_index, prev_log_term=prev_term,
                entries=list(entries), leader_commit=self.commit_index,
                lease_ttl=lease_ttl,
            ))
            self._inflight[peer_id] = self._inflight.get(peer_id, 0) + 1
            if match <= 0:
                return  # probe mode: do not advance next, await the ack
            self.next_index[peer_id] = next_idx + len(entries)
            sent += 1
        if sent == 0 and allow_empty:
            # heartbeat / commit-index propagation; also flows to paused
            # (snapshot-installing) peers so they neither campaign nor
            # starve the CheckQuorum lease of their responses
            prev_index = next_idx - 1
            prev_term = self._term_at(prev_index) if prev_index > 0 else 0
            self._send(AppendEntries(
                frm=self.id, to=peer_id, term=self.term,
                prev_log_index=prev_index, prev_log_term=prev_term,
                entries=[], leader_commit=self.commit_index,
                lease_ttl=lease_ttl,
            ))

    def _send_snapshot_to(self, peer_id: int):
        """Stream the current snapshot in chunks and pause the peer until
        it acks (or the TTL expires and we re-send)."""
        if peer_id in self._snap_pending:
            return
        from ..rpc import codec

        # serialize once per snapshot_index: snapshot_state() reads the
        # LIVE store, so a re-stream after new commits would otherwise
        # produce different bytes under the same snapshot_index
        if self._snap_blob is None or \
                self._snap_blob[0] != self.snapshot_index:
            self._snap_blob = (self.snapshot_index,
                               codec.dumps(self.snapshot_state()))
        blob = self._snap_blob[1]
        chunks = [blob[i:i + SNAPSHOT_CHUNK_BYTES]
                  for i in range(0, len(blob), SNAPSHOT_CHUNK_BYTES)] or [b""]
        members = {rid: (p.node_id, p.addr)
                   for rid, p in self.members.items()}
        removed = sorted(self.removed_ids)
        for seq, part in enumerate(chunks):
            self._send(SnapshotChunk(
                frm=self.id, to=peer_id, term=self.term,
                snapshot_index=self.snapshot_index,
                snapshot_term=self.snapshot_term,
                members=members, removed=removed,
                seq=seq, total=len(chunks), chunk=part,
            ))
        self.snap_chunks_sent += len(chunks)
        _snap_events.inc(("chunk_sent",), len(chunks))
        self._snap_pending[peer_id] = _SnapPending(
            snap_idx=self.snapshot_index,
            deadline=self.clock.monotonic() + self.snapshot_resend_seconds)
        self.next_index[peer_id] = self.snapshot_index + 1

    def _maybe_advance_commit(self):
        if self.role != LEADER:
            return
        matches = sorted(
            [self._last_index()]
            + [self.match_index.get(p, 0) for p in self.members if p != self.id],
            reverse=True,
        )
        voters = len(self.members) or 1
        quorum_match = matches[voters // 2] if voters > 1 else matches[0]
        # only commit entries from the current term directly (raft §5.4.2)
        if quorum_match > self.commit_index and \
                self._term_at(quorum_match) == self.term:
            self.commit_index = quorum_match
            self._mark_broadcast()  # propagate the new commit index
            # the flush applies right after this (batched apply pass)

    def _apply_committed(self):
        # disarmed cost on this hot loop: one truthiness test up front,
        # one `and`-short-circuited attribute read per entry
        traced = trace.enabled()
        if self.last_applied < self.commit_index:
            # persist the advanced commit (etcd HardState semantics: term,
            # vote and commit survive restarts together)
            self._persist_hard_state()
        while self.last_applied < self.commit_index:
            self.last_applied += 1
            idx = self.last_applied - self.first_index
            if idx < 0:
                continue  # covered by snapshot
            if idx >= len(self.log):
                # commit raced ahead of a truncated log; stop rather than crash
                self.last_applied -= 1
                break
            e = self.log[idx]
            self.commits_applied += 1
            _t0 = None
            if traced and e.trace is not None:
                # the apply span joins the proposal's trace — on the
                # leader AND on followers (the ctx rode the replicated
                # entry), which is what makes the causal
                # propose→fsync→apply chain cross node boundaries
                _t0 = time.perf_counter()
            if e.kind == ENTRY_CONF_CHANGE:
                self._apply_conf_change(e)
            elif e.data is not None:
                try:
                    self.apply_entry(e)
                except Exception:
                    log.exception("raft-%d: apply failed at %d", self.id, e.index)
            if _t0 is not None:
                # _t0 is non-None ONLY under the `traced` guard above —
                # same armed-only shape the lint's enabled() pattern
                # recognizes, one hop removed  # lint: allow(span-in-loop)
                trace.rec("raft.apply", time.perf_counter() - _t0,
                          parent=e.trace, node=self.id, index=e.index)
            cb = self._waits.pop(e.request_id, None) if e.request_id else None
            if cb is not None:
                try:
                    cb(True, "")
                except Exception:
                    log.exception("raft-%d: wait callback failed", self.id)
        if self.role == LEADER and not self._signalled \
                and self.last_applied >= self._barrier_index:
            # the new-term barrier (and everything before it) is applied:
            # leadership is now usable (raft.go:644-670 ordering)
            self._signalled = True
            self._notify_leadership(True)
        self._maybe_snapshot()

    def _apply_conf_change(self, e: Entry):
        # membership is updated copy-on-write: cross-thread readers (role
        # manager via member_by_node_id/can_remove_member) snapshot the dict
        # reference and iterate safely without locks
        cc: ConfChange = e.data
        if cc.action == "add":
            members = dict(self.members)
            members[cc.raft_id] = Peer(cc.raft_id, cc.node_id, cc.addr)
            self.members = members
            if self.role == LEADER and cc.raft_id != self.id:
                self.next_index.setdefault(cc.raft_id, self._last_index() + 1)
                self.match_index.setdefault(cc.raft_id, 0)
        elif cc.action == "remove":
            members = dict(self.members)
            members.pop(cc.raft_id, None)
            self.members = members
            self.removed_ids.add(cc.raft_id)
            self.next_index.pop(cc.raft_id, None)
            self.match_index.pop(cc.raft_id, None)
            if cc.raft_id == self.id and not self._self_removed:
                self._self_removed = True
                self._become_follower(self.term, None)
                if self.on_removed is not None:
                    # off-thread: the apply loop must not run teardown
                    threading.Thread(target=self.on_removed, daemon=True,
                                     name="raft-removed").start()
        if self.storage is not None:
            self.storage.save_membership(self.members, self.removed_ids)

    def _handle_self_removed(self):
        """Worker-thread handler for notify_removed: same consequences as
        applying our own removal conf change, minus a log entry we will
        never receive (peers stopped replicating to us)."""
        if self._self_removed:
            return
        self._self_removed = True
        members = dict(self.members)
        members.pop(self.id, None)
        self.members = members          # also stops further elections
        self.removed_ids.add(self.id)
        self._become_follower(self.term, None)
        if self.storage is not None:
            self.storage.save_membership(self.members, self.removed_ids)
        if self.on_removed is not None:
            threading.Thread(target=self.on_removed, daemon=True,
                             name="raft-removed").start()

    # -------------------------------------------------------------- snapshots
    def _maybe_snapshot(self):
        applied_in_log = self.last_applied - self.snapshot_index
        if applied_in_log < self.snapshot_interval:
            return
        data = self.snapshot_state()
        self.snapshot_term = self._term_at(self.last_applied)
        self.snapshot_index = self.last_applied
        keep_from = self.last_applied + 1 - self.first_index
        self.log = self.log[keep_from:]
        self.first_index = self.last_applied + 1
        if self.storage is not None:
            self.storage.save_snapshot(
                self.snapshot_index, self.snapshot_term, data, self.members,
                removed=self.removed_ids)
            self.storage.compact(self.first_index)

    # ------------------------------------------------------------ persistence
    def _persist_hard_state(self):
        """Mark term/vote/commit dirty; the batch flush writes hardstate at
        most once, and always before any buffered message leaves."""
        self._hs_dirty = True

    def _persist_entry(self, e: Entry):
        """Stage an entry for the batch flush's single group-commit WAL
        append (one write + one fsync for the whole batch)."""
        self._ready_entries.append(e)

    def _append_entry_storage_truncate(self, from_index: int):
        # conflict truncation: drop staged-but-unpersisted entries in the
        # truncated range too, then truncate the durable log
        self._ready_entries = [e for e in self._ready_entries
                               if e.index < from_index]
        if self.storage is not None:
            self.storage.truncate_from(from_index)

    def _restore_from_storage(self):
        state = self.storage.load()
        if state is None:
            return
        self.term = state.term
        self.voted_for = state.voted_for
        self.snapshot_index = state.snapshot_index
        self.snapshot_term = state.snapshot_term
        self.first_index = state.snapshot_index + 1
        self.log = list(state.entries)
        self.members = dict(state.members)
        self.removed_ids = set(state.removed)
        # a torn WAL tail (or undecryptable entries) can leave the persisted
        # commit ahead of the recovered log; cap it so replay can't index
        # past the entries we actually have
        self.commit_index = min(max(state.commit_index, state.snapshot_index),
                                self._last_index())
        self.last_applied = self.snapshot_index
        if state.snapshot_data is not None:
            self.restore_state(state.snapshot_data)
        self._apply_committed()

    # ----------------------------------------------------------------- helpers
    def _last_index(self) -> int:
        return self.first_index + len(self.log) - 1 if self.log else self.snapshot_index

    def _last_term(self) -> int:
        return self.log[-1].term if self.log else self.snapshot_term

    def _term_at(self, index: int) -> int:
        if index == self.snapshot_index:
            return self.snapshot_term
        i = index - self.first_index
        if 0 <= i < len(self.log):
            return self.log[i].term
        return -1

    def _send(self, msg):
        """Buffer an outgoing message; the batch flush releases it to the
        transport only AFTER the flush's WAL append + hardstate save, so
        no message ever claims state that is not yet durable."""
        self._out_msgs.append(msg)

    # -------------------------------------------------------------- lease
    def _lease_ttl(self) -> float:
        """Seconds of read lease this node may grant right now (0.0 =
        none). Only a SIGNALLED leader running CheckQuorum grants, and
        the grant is ANCHORED at the last observed quorum contact: it
        shrinks as that contact ages and hits zero after lease_duration
        of quorum silence — so a leader partitioned with a minority
        stops extending follower leases immediately, long before its
        CheckQuorum step-down, instead of stretching a stale follower's
        window past a new leader's election. The vote-withholding half
        (followers ignore campaigns for election_tick after leader
        contact) is what makes the window itself sound; like etcd's
        clock-based lease reads, the anchor assumes response delay is
        small against the window (arbitrarily delayed acks could
        freshen it — the strict alternative is ReadIndex round-trips)."""
        if not (self.lease_duration > 0.0 and self.role == LEADER
                and self._signalled and self.check_quorum):
            return 0.0
        remaining = self.lease_duration \
            - (self.clock.monotonic() - self._lease_quorum_contact)
        return max(0.0, min(remaining, self.lease_duration))

    def read_ok(self) -> bool:
        """May this node serve a lease-gated read right now? The leader
        always may. A follower may only while (a) it holds a live,
        skew-discounted lease from the CURRENT term's leader and (b) it
        has APPLIED at least the leader's commit index from the grant —
        the served snapshot is then no older than the leader's commit
        frontier at grant time (bounded staleness, not linearizability;
        writes stay leader-only). Thread-safe for RPC-thread callers."""
        if self.is_leader:
            return True
        if self.role != FOLLOWER or self._read_lease_term != self.term:
            return False
        if self.clock.monotonic() >= self._read_lease_until:
            return False
        return self.last_applied >= self._read_lease_index

    def read_lease(self) -> dict:
        """Introspection for status()/tests: the current lease triple
        plus the live verdict."""
        return {
            "ok": self.read_ok(),
            "until": self._read_lease_until,
            "index": self._read_lease_index,
            "term": self._read_lease_term,
            "applied": self.last_applied,
        }

    # ------------------------------------------------------------- introspect
    @property
    def is_leader(self) -> bool:
        """Usable leadership: elected AND the new-term barrier has applied
        (proposals before that point are rejected)."""
        return self.role == LEADER and self._signalled

    def status(self) -> dict:
        return {
            "id": self.id,
            "role": self.role,
            "term": self.term,
            "leader": self.leader_id,
            "commit": self.commit_index,
            "applied": self.last_applied,
            "last_index": self._last_index(),
            "members": {p.raft_id: p.addr for p in self.members.values()},
            # group-commit plane observability: amortized cost per commit
            # is wal_fsyncs / commits_applied when storage is attached
            "ready_flushes": self.ready_flushes,
            "ready_items": self.ready_items,
            "commits_applied": self.commits_applied,
            # fault plane: read-only degradation + append/hardstate
            # failures observed (tests and the operator surface read it)
            "storage_degraded": self.storage_degraded,
            "storage_errors": self.storage_errors,
            # read-lease plane (ISSUE 13): may this node serve
            # lease-gated reads, and under which grant
            "read_lease": self.read_lease(),
            # recovery plane (ISSUE 18): streamed-snapshot progress —
            # resent/resume stay near zero on a healthy network; installs
            # and their wall time size the catch-up path
            "snap_chunks_sent": self.snap_chunks_sent,
            "snap_chunks_resent": self.snap_chunks_resent,
            "snap_resume_suffix": self.snap_resume_suffix,
            "snap_chunks_rejected": self.snap_chunks_rejected,
            "snap_installs": self.snap_installs,
            "snap_install_seconds": self.snap_install_seconds,
        }
