"""Trace plane (ISSUE 5): the span tracer + flight recorder, its causal
propagation across threads / RPC / raft consensus, the disarmed
zero-allocation contract on the hot paths, the derived stage-latency
histograms, and the /metrics exposition satellites.

The acceptance pair:

  * a 3-node raft cluster produces ONE causal trace covering
    propose → WAL-fsync → apply on every node (the context rides the
    Entry through replication);
  * a failpoints-style guard pins that tracing OFF allocates no Span
    and files no record on the tick, dispatcher-flush, raft ready-flush,
    event-loop burst and scheduler drain hot paths.
"""
import importlib.util
import json
import os
import random
import threading
import time
import types
import urllib.request

import pytest

from swarmkit_tpu.utils import failpoints, trace
from swarmkit_tpu.utils.clock import FakeClock


# ------------------------------------------------------------ tracer core
def test_disarmed_surface_is_inert():
    assert not trace.active()
    assert trace.span("never.armed") is trace.NOOP
    assert trace.start("never.armed") is None
    assert trace.ctx() is None
    trace.rec("never.armed", 0.01)          # no-op, no error
    trace.annotate(never="armed")
    fn = object()
    assert trace.wrap("never.armed", fn) is fn
    assert trace.tail_text() == ""
    # NOOP singleton is safely usable everywhere a Span is
    with trace.span("x") as s:
        assert s.ctx() is None
        s.set(a=1).end()


def test_span_nesting_and_trees():
    with trace.armed() as rec:
        with trace.span("sched.tick", n=1) as root:
            with trace.span("tick.encode"):
                pass
            with trace.span("tick.dispatch"):
                pass
            root_ctx = root.ctx()
        # explicit parenting across threads
        done = threading.Event()

        def worker():
            with trace.span("tick.commit_heavy", parent=root_ctx):
                pass
            done.set()

        threading.Thread(target=worker, daemon=True).start()
        assert done.wait(5)
        trees = rec.trees()
    assert not trace.active()
    assert len(trees) == 1
    root = trees[0]
    assert root["name"] == "sched.tick" and root["attrs"] == {"n": 1}
    kids = sorted(c["name"] for c in root["children"])
    assert kids == ["tick.commit_heavy", "tick.dispatch", "tick.encode"]
    # every record shares the root's trace id
    assert {c["trace"] for c in root["children"]} == {root["trace"]}


def test_ring_is_bounded_and_counts_drops():
    with trace.armed(capacity=64) as rec:
        for i in range(500):
            trace.rec("tick.encode", 0.001, i=i)
        snap = rec.snapshot()
        assert len(snap) <= 64
        assert rec.dropped == 500 - len(snap)
        assert rec.spans_recorded == 500
        # the TAIL survived — crash forensics wants the newest spans
        assert snap[-1]["attrs"]["i"] == 499


def test_exception_exit_records_error_attr_and_unwinds_stack():
    with trace.armed() as rec:
        with pytest.raises(ValueError):
            with trace.span("tick.encode"):
                raise ValueError("boom")
        assert trace.ctx() is None          # stack unwound
        (r,) = rec.snapshot()
        assert "ValueError" in r["attrs"]["error"]


def test_clock_injection_stamps_fake_time():
    clock = FakeClock(start=5000.0)
    with trace.armed(clock=clock) as rec:
        trace.rec("tick.encode", 0.25)
        (r,) = rec.snapshot()
        assert r["t0"] == pytest.approx(5000.0 - 0.25)
        # window filtering rides the same injected clock
        clock.advance(100.0)
        assert rec.snapshot(seconds=10.0) == []
        assert rec.snapshot(seconds=200.0) == [r]
        # windows key on RETIRE time: a span longer than the window
        # (the slow stage an operator hunts) must still show up
        trace.rec("tick.barrier", 150.0)     # started long ago, just ended
        assert [x["name"] for x in rec.snapshot(seconds=10.0)] \
            == ["tick.barrier"]


def test_wrap_links_commit_worker_job_to_wave_span():
    from swarmkit_tpu.ops.commit import CommitWorker

    with trace.armed() as rec:
        sp = trace.start("tick.wave")
        ran = {}

        def job():
            ran["thread"] = threading.current_thread().name
            # spans the job opens must NEST under the wrap span (the
            # heavy-commit sub-stages in Scheduler._commit_heavy do
            # exactly this) — not become orphan roots
            with trace.span("tick.commit.materialize"):
                pass

        w = CommitWorker(name="trace-test-worker")
        try:
            w.submit(trace.wrap("tick.commit_heavy", job, parent=sp))
            w.barrier()
        finally:
            w.close()
        sp.end()
        recs = {r["name"]: r for r in rec.snapshot()}
    heavy = recs["tick.commit_heavy"]
    assert ran["thread"] == "trace-test-worker"
    assert heavy["thread"] == "trace-test-worker"
    assert heavy["parent"] == recs["tick.wave"]["span"]
    assert heavy["trace"] == recs["tick.wave"]["trace"]
    sub = recs["tick.commit.materialize"]
    assert sub["parent"] == heavy["span"]
    assert sub["trace"] == recs["tick.wave"]["trace"]


def test_malformed_wire_ctx_never_raises():
    """Entry.trace / the RPC _trace_ctx kwarg arrive off the wire: a
    version-skewed peer's garbage ctx must degrade to 'no parent', not
    raise inside the consumer's apply loop (which would wedge commit
    application on that node while tracing is armed)."""
    from swarmkit_tpu.raft.messages import Entry
    from swarmkit_tpu.raft.testutils import RaftCluster

    with trace.armed() as rec:
        for bad in (5, "just-a-string", ["one"], ("a", "b", "c"),
                    (1, 2), {"t": "x"}, (None, "y")):
            trace.rec("raft.apply", 0.001, parent=bad)
            trace.start("raft.propose", parent=bad).end()
            with trace.span("rpc.server", parent=bad):
                pass
        assert rec.spans_recorded == 3 * 7  # all filed, none raised
        # end-to-end: a committed entry carrying a garbage ctx still
        # applies (the leader below echoes whatever rides the proposal)
        cluster = RaftCluster(3, seed=31)
        leader = cluster.elect(1)
        res = {}
        leader.propose({"k": 1}, "bad-ctx",
                       lambda ok, err: res.update(ok=ok),
                       trace_ctx=["not", "a", "valid", "ctx"])
        cluster.settle()
        assert res.get("ok") is True
        assert all(n.last_applied == n.commit_index
                   for n in cluster.nodes.values())


def test_retired_tail_survives_disarm_for_report_hooks():
    """The chaos harness disarms inside the test body; the conftest
    report hook still needs the tail — disarm() retires it into
    last_tail_text(), and clear_retired_tail() (run by the autouse
    fixture before every test) prevents stale carry-over."""
    with trace.armed():
        trace.rec("tick.barrier", 0.25, wave=3)
    assert trace.tail_text() == ""          # disarmed: the strict surface
    assert "tick.barrier" in trace.last_tail_text()
    assert "wave=3" in trace.last_tail_text()
    trace.clear_retired_tail()
    assert trace.last_tail_text() == ""


def test_stage_histograms_derived_from_spans():
    from swarmkit_tpu.utils.metrics import histogram_family

    tick_fam = histogram_family("tick_stage_seconds")
    raft_fam = histogram_family("raft_commit_path_seconds")
    disp_fam = histogram_family("dispatcher_flush_seconds")
    n_encode = tick_fam.child(("encode",))._n
    n_fsync = raft_fam.child(("wal_fsync",))._n
    n_wheel = disp_fam.child(("wheel.tick",))._n
    n_apply = raft_fam.child(("apply",))._n
    with trace.armed():
        trace.rec("tick.encode", 0.002)
        trace.rec("raft.wal_fsync", 0.001)
        trace.rec("hb.wheel.tick", 0.0005)
        # zero-duration records are markers, never latency samples
        trace.rec("raft.apply", 0.0, node=1)
    assert tick_fam.child(("encode",))._n == n_encode + 1
    assert raft_fam.child(("wal_fsync",))._n == n_fsync + 1
    assert disp_fam.child(("wheel.tick",))._n == n_wheel + 1
    assert raft_fam.child(("apply",))._n == n_apply


# ------------------------------------------- disarmed-overhead acceptance
class _SpanAllocGuard:
    """Failpoints-style op-count guard: with tracing off, NO Span may be
    constructed and NO record filed anywhere in the exercised paths —
    the assertion fires at the allocation site, naming the culprit."""

    def __enter__(self):
        def _boom(*a, **k):
            raise AssertionError(
                "disarmed hot path allocated a trace span/record")

        self._span_init = trace.Span.__init__
        self._rec_record = trace.FlightRecorder.record
        trace.Span.__init__ = _boom
        trace.FlightRecorder.record = _boom
        return self

    def __exit__(self, *exc):
        trace.Span.__init__ = self._span_init
        trace.FlightRecorder.record = self._rec_record


def test_disarmed_zero_allocation_on_raft_ready_loop():
    """The raft worker's dispatch + flush + apply path (group-commit
    plane) with tracing off: proposals, elections, replication — zero
    span traffic."""
    from swarmkit_tpu.raft.testutils import RaftCluster

    assert not trace.active()
    with _SpanAllocGuard():
        cluster = RaftCluster(3, seed=11)
        cluster.elect(1)
        for i in range(5):
            assert cluster.propose({"k": i})
        cluster.tick_all(3)


def test_disarmed_zero_allocation_on_dispatcher_flush(tmp_path):
    """The fan-out flush + heartbeat-wheel path with tracing off."""
    from test_dispatcher_fanout import driven_dispatcher, mk_node, pump

    from swarmkit_tpu.api.objects import Task
    from swarmkit_tpu.api.types import TaskState
    from swarmkit_tpu.store.memory import MemoryStore

    assert not trace.active()
    try:
        with _SpanAllocGuard():
            store = MemoryStore()
            d, ch = driven_dispatcher(store)
            mk_node(store, "n1")
            sid = d.register("n1")
            d.assignments("n1", sid)
            t = Task(id="t1", node_id="n1")
            t.status.state = TaskState.ASSIGNED
            store.update(lambda tx: tx.create(t))
            pump(d, ch)
            d._send_incrementals()
            assert d.heartbeat("n1", sid) > 0
            # drive the wheel ticker once too
            d._hb_wheel._tick(d._hb_wheel._ticker_gen)
    finally:
        d._hb_wheel.stop()


def test_disarmed_zero_allocation_on_pipelined_tick():
    """The TickPipeline wave loop (encode/dispatch/pull/fold/commit,
    async commit plane) with tracing off."""
    from test_pipeline import run_pipelined_trace

    assert not trace.active()
    with _SpanAllocGuard():
        run_pipelined_trace(3, steps=4, depth=1, async_commit=True)


def test_failing_wave_span_reaches_recorder():
    """A tick that dies (poisoned commit plane re-raising at the
    barrier) must still file its tick.wave span WITH the error — the
    failing wave is exactly the forensics payload the wedge/chaos tail
    exists to show."""
    import random as _random

    from test_encoder_incremental import make_info
    from test_pipeline import make_commit, make_waves
    from test_placement_parity import random_group

    from swarmkit_tpu.ops.pipeline import TickPipeline
    from swarmkit_tpu.ops.resident import ResidentPlacement
    from swarmkit_tpu.scheduler.encode import IncrementalEncoder

    rng = _random.Random(0)
    infos = [make_info(rng, i) for i in range(6)]
    enc = IncrementalEncoder()
    rp = ResidentPlacement(enc)
    pipe = TickPipeline(enc, rp, make_commit(infos), depth=1,
                        async_commit=True)

    def boom():
        raise RuntimeError("injected heavy-commit crash")

    with trace.armed() as rec:
        try:
            pipe.tick(infos, make_waves(rng, 0, random_group))
            pipe.worker.submit(boom)     # poison the plane
            with pytest.raises(RuntimeError):
                pipe.tick(infos, make_waves(rng, 1, random_group))
            waves = [r for r in rec.snapshot()
                     if r["name"] == "tick.wave"]
            assert any("RuntimeError" in r["attrs"].get("error", "")
                       for r in waves), waves
        finally:
            pipe.worker.reset()
            pipe.close()


def test_disarmed_zero_allocation_on_raft_ready_flush_with_wal(tmp_path):
    """The ready flush with a WAL behind it (append + fsync, commit
    advance, apply) and the proposer's propose path, tracing off."""
    from swarmkit_tpu.raft.proposer import RaftProposer
    from swarmkit_tpu.raft.storage import RaftStorage
    from swarmkit_tpu.raft.testutils import RaftCluster

    assert not trace.active()
    with _SpanAllocGuard():
        cluster = RaftCluster(
            1, storages={1: RaftStorage(str(tmp_path / "n1"))}, seed=7)
        proposer = RaftProposer(cluster.nodes[1])
        cluster.elect(1)
        handles = [proposer.propose_async([], lambda **kw: None)
                   for _ in range(3)]
        cluster.settle()
        assert all(h.done for h in handles)


class _BurstProbe:
    """An EventLoopComponent that counts its handle and flush calls."""

    @staticmethod
    def make(store):
        from swarmkit_tpu.orchestrator.base import EventLoopComponent

        class Probe(EventLoopComponent):
            name = "burst-probe"

            def __init__(self, store):
                super().__init__(store)
                self.handled = 0
                self.flushes = 0

            def handle(self, event):
                self.handled += 1

            def flush_events(self):
                self.flushes += 1

        return Probe(store)


def _create_tasks(store, prefix, n):
    from swarmkit_tpu.api.objects import Task

    def run(tx):
        for i in range(n):
            tx.create(Task(id=f"{prefix}-{i:03d}"))

    store.update(run)


def _wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


def test_disarmed_zero_allocation_on_event_loop_burst():
    """EventLoopComponent's drained burst (handle calls + flush_events)
    with tracing off."""
    from swarmkit_tpu.store.memory import MemoryStore

    assert not trace.active()
    store = MemoryStore()
    with _SpanAllocGuard():
        probe = _BurstProbe.make(store)
        probe.start()
        try:
            _create_tasks(store, "quiet", 20)
            _wait_for(lambda: probe.handled >= 20)
        finally:
            probe.stop()
        assert probe.flushes >= 1


def _scheduler_store(n_nodes=4):
    from swarmkit_tpu.api.objects import Node
    from swarmkit_tpu.api.types import NodeAvailability, NodeStatusState
    from swarmkit_tpu.store.memory import MemoryStore

    store = MemoryStore()

    def seed(tx):
        for i in range(n_nodes):
            n = Node(id=f"n{i:02d}")
            n.status.state = NodeStatusState.READY
            n.spec.availability = NodeAvailability.ACTIVE
            tx.create(n)

    store.update(seed)
    return store


def _pending_tasks(store, prefix, n):
    from swarmkit_tpu.api.objects import Task
    from swarmkit_tpu.api.types import TaskState

    def run(tx):
        for w in range(n):
            t = Task(id=f"{prefix}-{w:02d}", service_id="s1", slot=w + 1)
            t.desired_state = TaskState.RUNNING
            t.status.state = TaskState.PENDING
            tx.create(t)

    store.update(run)


def _all_assigned(store, prefix, n):
    from swarmkit_tpu.api.types import TaskState

    mine = [t for t in store.view(lambda tx: tx.find_tasks())
            if t.id.startswith(prefix)]
    return len(mine) == n and all(
        t.status.state == TaskState.ASSIGNED and t.node_id for t in mine)


def test_disarmed_zero_allocation_on_scheduler_drain():
    """Scheduler.run's event drain between ticks (and the ticks it
    triggers) with tracing off."""
    from swarmkit_tpu.scheduler.scheduler import Scheduler

    assert not trace.active()
    store = _scheduler_store()
    with _SpanAllocGuard():
        sched = Scheduler(store, backend="cpu")
        sched.start()
        try:
            _pending_tasks(store, "quiet", 6)
            _wait_for(lambda: _all_assigned(store, "quiet", 6), 30.0)
        finally:
            sched.stop()


# ------------------------------------------------- raft causal trace (3n)
def test_raft_3node_causal_trace_propose_fsync_apply(tmp_path):
    """Acceptance: ONE causal trace covers propose → WAL-fsync → apply
    on every node — the context rides the replicated Entry, so each
    node's ready flush (and the fsync inside it) and its apply span share
    the leader-side proposal's trace id."""
    from swarmkit_tpu.raft.storage import RaftStorage
    from swarmkit_tpu.raft.testutils import RaftCluster

    storages = {i: RaftStorage(str(tmp_path / f"n{i}")) for i in (1, 2, 3)}
    cluster = RaftCluster(3, storages=storages, seed=23)
    leader = cluster.elect(1)

    with trace.armed() as rec:
        sp = trace.start("raft.propose")
        result = {}
        leader.propose({"op": "traced"}, "req-traced",
                       lambda ok, err: result.update(ok=ok, err=err),
                       trace_ctx=sp.ctx())
        cluster.settle()
        assert result.get("ok"), result
        sp.end(ok=True)
        recs = rec.snapshot()

    mine = [r for r in recs if r["trace"] == sp.trace_id]
    by_name = {}
    for r in mine:
        by_name.setdefault(r["name"], []).append(r)
    # the full causal chain, in one trace
    for stage in ("raft.propose", "raft.ready", "raft.wal_fsync",
                  "raft.apply"):
        assert stage in by_name, (stage, sorted(by_name))
    # across node boundaries: the entry replicated with its ctx, so every
    # member persisted and applied under THIS trace
    fsync_nodes = {r["attrs"]["node"] for r in by_name["raft.wal_fsync"]}
    apply_nodes = {r["attrs"]["node"] for r in by_name["raft.apply"]}
    assert fsync_nodes == {1, 2, 3}
    assert apply_nodes == {1, 2, 3}
    # parent links: each node's flush and apply point at the proposal,
    # each fsync at its own node's flush
    assert {r["parent"] for r in by_name["raft.ready"]} == {sp.span_id}
    assert {r["parent"] for r in by_name["raft.apply"]} == {sp.span_id}
    ready = {r["span"]: r["attrs"]["node"] for r in by_name["raft.ready"]}
    for r in by_name["raft.wal_fsync"]:
        assert ready[r["parent"]] == r["attrs"]["node"]
    # the removed per-entry point events stay gone
    assert not {"raft.stage", "raft.commit"} & {r["name"] for r in recs}


def test_entry_trace_ctx_survives_wire_codec():
    """The ctx crosses REAL node boundaries via codec (AppendEntries and
    the WAL encode entries field-by-field); pre-trace payloads decode
    with the default."""
    from swarmkit_tpu.raft.messages import Entry
    from swarmkit_tpu.rpc import codec

    e = Entry(term=2, index=7, data={"x": 1}, request_id="r1",
              trace=("aabbccdd00112233", "deadbeef44556677"))
    back = codec.loads(codec.dumps(e))
    assert back.trace == e.trace and isinstance(back.trace, tuple)
    # an old-format entry (no trace field) still constructs
    legacy = codec.loads(codec.dumps(Entry(term=1, index=1)))
    assert legacy.trace is None


def test_proposer_opens_propose_root_span(tmp_path):
    """RaftProposer.propose_async: the store's write path gets its root
    span for free; resolve closes it."""
    from swarmkit_tpu.raft.proposer import RaftProposer
    from swarmkit_tpu.raft.testutils import RaftCluster
    from swarmkit_tpu.store.memory import StoreAction

    cluster = RaftCluster(1, seed=5)
    node = cluster.nodes[1]
    proposer = RaftProposer(node)
    cluster.elect(1)
    with trace.armed() as rec:
        fired = []
        handle = proposer.propose_async([], lambda **kw: fired.append(kw))
        cluster.settle()
        assert handle.done and fired
        names = [r["name"] for r in rec.snapshot()]
    assert "raft.propose" in names


def test_propose_span_counts_actions_under_callers_span():
    """raft.propose carries the proposal's action count and parents to
    the caller's open span: the layer that proposed is on record."""
    from swarmkit_tpu.raft.proposer import RaftProposer
    from swarmkit_tpu.raft.testutils import RaftCluster

    cluster = RaftCluster(1, seed=5)
    proposer = RaftProposer(cluster.nodes[1])
    cluster.elect(1)
    with trace.armed() as rec:
        with trace.span("loop.allocator") as loop_sp:
            handle = proposer.propose_async(
                iter([{"a": 1}, {"a": 2}, {"a": 3}]), lambda **kw: None)
        cluster.settle()
        assert handle.done
        recs = {r["name"]: r for r in rec.snapshot()}
    prop = recs["raft.propose"]
    assert prop["attrs"]["actions"] == 3
    assert prop["parent"] == loop_sp.span_id
    # filed under the thread that proposed
    assert prop["thread"] == recs["loop.allocator"]["thread"]


def test_raft_ready_span_wraps_fsync_and_apply(tmp_path):
    """One raft.ready per flush, with the batch size; the group fsync is
    its child, and the apply span nests inside it on the same thread."""
    from swarmkit_tpu.raft.proposer import RaftProposer
    from swarmkit_tpu.raft.storage import RaftStorage
    from swarmkit_tpu.raft.testutils import RaftCluster

    cluster = RaftCluster(
        1, storages={1: RaftStorage(str(tmp_path / "n1"))}, seed=9)
    proposer = RaftProposer(cluster.nodes[1])
    cluster.elect(1)
    with trace.armed() as rec:
        handles = [proposer.propose_async([{"k": i}], lambda **kw: None)
                   for i in range(3)]
        cluster.settle()
        assert all(h.done for h in handles)
        recs = rec.snapshot()
    readies = [r for r in recs if r["name"] == "raft.ready"]
    fsyncs = [r for r in recs if r["name"] == "raft.wal_fsync"]
    applies = [r for r in recs if r["name"] == "raft.apply"]
    assert readies and fsyncs and len(applies) == 3
    by_span = {r["span"]: r for r in readies}
    # every entry went through one flush's WAL append
    assert sum(r["attrs"]["entries"] for r in readies) >= 3
    for f in fsyncs:
        parent = by_span[f["parent"]]
        assert f["attrs"]["entries"] == parent["attrs"]["entries"]
        assert parent["t0"] <= f["t0"]
        assert f["t0"] + f["dur"] <= parent["t0"] + parent["dur"] + 1e-6
    props = {r["span"] for r in recs if r["name"] == "raft.propose"}
    for a in applies:
        assert a["parent"] in props
        assert any(r["thread"] == a["thread"] and r["t0"] <= a["t0"]
                   and a["t0"] + a["dur"] <= r["t0"] + r["dur"] + 1e-6
                   for r in readies)


def test_event_loop_burst_span_per_burst():
    """EventLoopComponent: one loop.<name> span per drained burst, its
    events attr the burst's size — never one span per event."""
    from swarmkit_tpu.store.memory import MemoryStore

    store = MemoryStore()
    with trace.armed() as rec:
        probe = _BurstProbe.make(store)
        probe.start()
        try:
            _create_tasks(store, "loud", 20)
            _wait_for(lambda: probe.handled >= 20)
        finally:
            probe.stop()
        spans = [r for r in rec.snapshot()
                 if r["name"] == "loop.burst-probe"]
    assert len(spans) == probe.flushes
    assert sum(r["attrs"]["events"] for r in spans) == probe.handled
    # one transaction's events arrive together: fewer bursts than events
    assert len(spans) < probe.handled
    assert all(r["thread"] == "burst-probe" for r in spans)


def test_sched_drain_span_counts_handled_events():
    """Scheduler.run: one sched.drain span per burst handled between
    ticks, with the events it handled; files under tick_stage_seconds."""
    from swarmkit_tpu.scheduler.scheduler import Scheduler
    from swarmkit_tpu.utils.metrics import histogram_family

    fam = histogram_family("tick_stage_seconds")
    n_drain = fam.child(("drain",))._n
    store = _scheduler_store()
    with trace.armed() as rec:
        sched = Scheduler(store, backend="cpu")
        handled = []
        orig = sched._handle

        def counting(ev):
            handled.append(ev)
            return orig(ev)

        sched._handle = counting
        sched.start()
        try:
            _pending_tasks(store, "drain", 6)
            _wait_for(lambda: _all_assigned(store, "drain", 6), 30.0)
        finally:
            sched.stop()
        recs = rec.snapshot()
    drains = [r for r in recs if r["name"] == "sched.drain"]
    assert drains
    assert sum(r["attrs"]["events"] for r in drains) == len(handled)
    assert all(r["thread"] == "scheduler" for r in drains)
    assert fam.child(("drain",))._n > n_drain
    # drains and ticks take turns on one thread: never nested
    ticks = [r for r in recs if r["name"] == "sched.tick"]
    for d in drains:
        assert d["parent"] is None or d["parent"] not in {
            t["span"] for t in ticks}


def test_span_cpu_time_against_wall_time():
    """Every span records its thread's CPU seconds beside the wall
    seconds: near the wall time for a busy span, near zero for one that
    waits; pre-measured stages and cross-thread spans carry None."""
    with trace.armed() as rec:
        with trace.span("busy"):
            t = time.perf_counter()
            x = 0
            while time.perf_counter() - t < 0.05:
                x += 1
        with trace.span("waits"):
            time.sleep(0.05)
        trace.rec("premeasured", 0.01)
        sp = trace.start("crosses")
        th = threading.Thread(target=sp.end)
        th.start()
        th.join()
        recs = {r["name"]: r for r in rec.snapshot()}
    busy, waits = recs["busy"], recs["waits"]
    assert 0 < busy["cpu"] <= busy["dur"]
    assert busy["cpu"] > 0.5 * busy["dur"]
    assert 0 <= waits["cpu"] < 0.5 * waits["dur"]
    assert recs["premeasured"]["cpu"] is None
    assert recs["crosses"]["cpu"] is None


def test_span_ids_unique_with_process_prefix():
    """Ids are a per-process random prefix plus a counter: unique within
    the process (threads included), and another process draws another
    prefix."""
    import subprocess
    import sys

    def open_spans():
        for _ in range(200):
            with trace.span("a"):
                pass

    with trace.armed() as rec:
        threads = [threading.Thread(target=open_spans) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
        recs = rec.snapshot()
    ids = [r["span"] for r in recs] + [r["trace"] for r in recs]
    assert len(ids) == 2 * 800 and len(set(ids)) == len(ids)
    assert all(i.startswith(trace._ID_PREFIX) for i in ids)
    other = subprocess.run(
        [sys.executable, "-c",
         "from swarmkit_tpu.utils import trace; print(trace._ID_PREFIX)"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert other.returncode == 0, other.stderr
    assert other.stdout.strip() not in ("", trace._ID_PREFIX)


def test_retired_recorder_readable_after_disarm():
    """A traced window's readers run after the window closed: the last
    disarmed recorder stays readable until cleared."""
    with trace.armed() as rec:
        trace.rec("tick.encode", 0.001)
    assert trace.retired() is rec
    assert [r["name"] for r in trace.retired().snapshot()] == [
        "tick.encode"]
    trace.clear_retired_tail()
    assert trace.retired() is None


def test_dispatch_span_carries_program_shapes():
    """ResidentPlacement stamps the static shapes of the tick and pull
    programs onto the caller's tick.dispatch span, with new_shape the
    first time it dispatches a key."""
    import random as _random

    from test_encoder_incremental import NOW, make_info
    from test_resident import apply_tick, plain_group

    from swarmkit_tpu.ops.resident import ResidentPlacement
    from swarmkit_tpu.scheduler.encode import IncrementalEncoder

    rng = _random.Random(7)
    infos = [make_info(rng, i) for i in range(16)]
    enc = IncrementalEncoder()
    rp = ResidentPlacement(enc)
    with trace.armed() as rec:
        p1 = enc.encode(infos, [plain_group("steady", 1, 8)], now=NOW)
        with trace.span("tick.dispatch"):
            h = rp.schedule_async(p1)
        apply_tick(enc, rp, infos, p1, h.get())
        p2 = enc.encode(infos, [plain_group("steady", 2, 8)], now=NOW)
        with trace.span("tick.dispatch"):
            rp.schedule_async(p2).get()
        first, second = [r["attrs"] for r in rec.snapshot()
                         if r["name"] == "tick.dispatch"]
    assert first["new_shape"] is True
    assert first["new_tick"] and first["new_pull"]
    assert len(first["tick_shape"]) == 10 and len(first["tick_flags"]) == 6
    assert first["pull"][0] in ("sparse", "slice")
    assert first["pull"][1:3] == (1, 16)
    # the same shapes again: no new_shape
    assert second["tick_shape"] == first["tick_shape"]
    assert second["pull"] == first["pull"]
    assert "new_shape" not in second


# ----------------------------------------------------- rpc span propagation
def _stub_security():
    from swarmkit_tpu.api.types import NodeRole

    return types.SimpleNamespace(identity=types.SimpleNamespace(
        node_id="srv", role=NodeRole.MANAGER, org="test-org"))


def test_rpc_client_server_spans_share_one_trace(tmp_path):
    """The client span's ctx rides the reserved `_trace_ctx` kwarg; the
    server opens its handler span under it — one trace per call. The
    handler must never see the reserved key."""
    from swarmkit_tpu.api.types import NodeRole
    from swarmkit_tpu.rpc.client import RPCClient
    from swarmkit_tpu.rpc.server import RPCServer, ServiceRegistry

    seen = {}

    def echo(caller, x, **kwargs):
        seen["kwargs"] = dict(kwargs)
        return x

    reg = ServiceRegistry()
    reg.add("t.echo", echo, roles=[NodeRole.MANAGER])
    srv = RPCServer("", _stub_security(), reg,
                    unix_path=str(tmp_path / "rpc.sock"))
    srv.start()
    client = RPCClient(srv.addr)
    try:
        with trace.armed() as rec:
            assert client.call("t.echo", 42) == 42
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                recs = {r["name"]: r for r in rec.snapshot()}
                if {"rpc.client", "rpc.server"} <= set(recs):
                    break
                time.sleep(0.01)
        assert seen["kwargs"] == {}         # reserved key stripped
        assert recs["rpc.server"]["trace"] == recs["rpc.client"]["trace"]
        assert recs["rpc.server"]["parent"] == recs["rpc.client"]["span"]
        assert recs["rpc.client"]["attrs"]["method"] == "t.echo"
    finally:
        client.close()
        srv.stop()


def test_rpc_traced_client_untraced_server_strips_key(tmp_path):
    """Arm only around the SEND: the server end must still strip the
    reserved kwarg even when its own tracer is disarmed (per-process
    arming is independent)."""
    from swarmkit_tpu.api.types import NodeRole
    from swarmkit_tpu.rpc.client import RPCClient
    from swarmkit_tpu.rpc.server import RPCServer, ServiceRegistry

    seen = {}

    def echo(caller, x, **kwargs):
        seen["kwargs"] = dict(kwargs)
        # the server-side handler runs with tracing disarmed in this
        # process only if disarm raced the call; either way the key is
        # never visible here
        return x

    reg = ServiceRegistry()
    reg.add("t.echo", echo, roles=[NodeRole.MANAGER])
    srv = RPCServer("", _stub_security(), reg,
                    unix_path=str(tmp_path / "rpc2.sock"))
    srv.start()
    client = RPCClient(srv.addr)
    try:
        with trace.armed():
            assert client.call("t.echo", 1) == 1
        assert seen["kwargs"] == {}
    finally:
        client.close()
        srv.stop()


# ------------------------------------------------ dispatcher + wheel spans
def test_dispatcher_flush_span_with_substages():
    from test_dispatcher_fanout import driven_dispatcher, mk_node, pump

    from swarmkit_tpu.api.objects import Task
    from swarmkit_tpu.api.types import TaskState
    from swarmkit_tpu.store.memory import MemoryStore

    store = MemoryStore()
    d, ch = driven_dispatcher(store)
    mk_node(store, "n1")
    sid = d.register("n1")
    d.assignments("n1", sid)
    t = Task(id="t1", node_id="n1")
    t.status.state = TaskState.ASSIGNED
    store.update(lambda tx: tx.create(t))
    pump(d, ch)
    try:
        with trace.armed() as rec:
            d._send_incrementals()
            recs = {r["name"]: r for r in rec.snapshot()}
    finally:
        d._hb_wheel.stop()
    flush = recs["dispatcher.flush"]
    assert flush["attrs"]["sessions"] == 1
    assert flush["attrs"]["served"] == 1
    for sub in ("dispatcher.flush.snapshot", "dispatcher.flush.serve"):
        assert recs[sub]["parent"] == flush["span"]
        assert recs[sub]["trace"] == flush["trace"]


def test_heartbeat_wheel_tick_span_under_fake_clock():
    from swarmkit_tpu.dispatcher.heartbeat import HeartbeatWheel

    clock = FakeClock()
    wheel = HeartbeatWheel(granularity=0.5, clock=clock)
    expired = []
    wheel.add("k1", 1.0, lambda: expired.append("k1"))
    with trace.armed() as rec:
        clock.advance(2.0)
        assert expired == ["k1"]
        recs = [r for r in rec.snapshot() if r["name"] == "hb.wheel.tick"]
    wheel.stop()
    assert recs and recs[-1]["attrs"]["fired"] == 1


# ------------------------------------------------------ wedge trace dump
def _load_module(relpath, name):
    """Load a module straight from its file under a dotted name (so its
    relative imports resolve) WITHOUT importing its package __init__ —
    the manager/node packages pull in the CA stack, which needs the
    optional `cryptography` wheel (same trick as test_debug_profile)."""
    import swarmkit_tpu

    path = os.path.join(os.path.dirname(swarmkit_tpu.__file__), relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_wedge_monitor_dumps_recorder_tail():
    WedgeMonitor = _load_module(os.path.join("manager", "wedge.py"),
                                "swarmkit_tpu.manager.wedge").WedgeMonitor

    store = types.SimpleNamespace(wedged=lambda: True, wedge_timeout=1.0)
    mon = WedgeMonitor(store, raft_node=None, check_interval=0.01)
    with trace.armed():
        trace.rec("tick.barrier", 0.5, wave=7)
        mon.start()
        deadline = time.monotonic() + 5
        while mon.fired == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        mon.stop()
        assert mon.fired >= 1
        assert "tick.barrier" in mon.last_trace_tail
        assert "wave=7" in mon.last_trace_tail


# ------------------------------------------------- /metrics satellites
def test_counter_and_histogram_family_render_under_concurrent_writers():
    """Satellite: scrape mid-increment must parse — the render takes a
    consistent snapshot while writer threads hammer the families."""
    from swarmkit_tpu.utils.metrics import CounterFamily, HistogramFamily

    cf = CounterFamily("fuzz_counter_total", "fuzz", ("op", "code"))
    hf = HistogramFamily("fuzz_seconds", "fuzz", ("op",))
    stop = threading.Event()

    def writer(seed):
        rng = random.Random(seed)
        while not stop.is_set():
            cf.inc((f"op{rng.randrange(4)}", f"c{rng.randrange(3)}"))
            hf.observe((f"op{rng.randrange(4)}",), rng.random() * 0.1)

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(50):
            for text in (cf.prometheus_text(), hf.prometheus_text()):
                _assert_prometheus_parses(text)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    # cumulative-bucket sanity on the final quiescent render
    _assert_prometheus_parses(hf.prometheus_text(), strict_buckets=True)


def _assert_prometheus_parses(text, strict_buckets=False):
    last_bucket = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert not line.startswith("#"), line
        name_part, _, value = line.rpartition(" ")
        assert name_part, line
        float(value)                       # parses as a sample value
        if strict_buckets and "_bucket{" in line:
            series = name_part.split('le="')[0]
            cur = float(value)
            assert cur >= last_bucket.get(series, 0.0), line
            last_bucket[series] = cur


def test_label_value_escaping_is_pinned():
    from swarmkit_tpu.utils.metrics import CounterFamily

    cf = CounterFamily("esc_total", "escaping pin", ("v",))
    cf.inc(('quo"te\\back\nline',))
    text = cf.prometheus_text()
    assert '# HELP esc_total escaping pin' in text
    assert 'esc_total{v="quo\\"te\\\\back\\nline"} 1' in text


def test_every_family_and_histogram_emits_help():
    from swarmkit_tpu.utils.metrics import (
        all_families,
        all_histograms,
        histogram,
    )

    histogram("help_probe_seconds", "probe help")
    for h in all_histograms():
        text = h.prometheus_text()
        assert text.startswith(f"# HELP {h.name} "), h.name
    for f in all_families():
        text = f.prometheus_text()
        assert text.startswith(f"# HELP {f.name} "), f.name


# ------------------------------------------------------- debug server
def _load_debugserver():
    return _load_module(os.path.join("node", "debugserver.py"),
                        "swarmkit_tpu.node.debugserver")


def _stub_node():
    from swarmkit_tpu.dispatcher.dispatcher import Dispatcher
    from swarmkit_tpu.store.memory import MemoryStore

    store = MemoryStore()
    store.view(lambda tx: tx.find_tasks())     # op_counts non-empty
    node = types.SimpleNamespace(
        node_id="stub", addr="127.0.0.1:0", is_leader=False,
        store=store, raft=None, manager=None,
        dispatcher=Dispatcher(store, heartbeat_period=300.0),
    )
    return node


def test_debugserver_binds_loopback_by_default():
    DebugServer = _load_debugserver().DebugServer

    srv = DebugServer(":0", _stub_node())
    try:
        host = srv._httpd.server_address[0]
        assert host == "127.0.0.1"
    finally:
        srv.stop()


def test_debugserver_metrics_content_type_help_and_components():
    DebugServer = _load_debugserver().DebugServer

    srv = DebugServer("127.0.0.1:0", _stub_node())
    srv.start()
    try:
        resp = urllib.request.urlopen(f"http://{srv.addr}/metrics")
        ctype = resp.headers.get("Content-Type")
        assert ctype.startswith("text/plain; version=0.0.4")
        text = resp.read().decode()
        # exported-through-/metrics satellites
        assert "swarm_store_ops_total{" in text
        assert "swarm_heartbeat_wheel_entries" in text
        # every family carries HELP
        for line in text.splitlines():
            if line.startswith("# TYPE "):
                name = line.split()[2]
                assert f"# HELP {name} " in text, name
    finally:
        srv.stop()


def test_debugserver_vars_exposes_opcounts_and_arm_state():
    DebugServer = _load_debugserver().DebugServer

    srv = DebugServer("127.0.0.1:0", _stub_node())
    srv.start()
    try:
        with failpoints.armed("probe.site"):
            with trace.armed():
                v = json.loads(urllib.request.urlopen(
                    f"http://{srv.addr}/debug/vars").read())
        assert v["failpoints_armed"] == ["probe.site"]
        assert v["trace_armed"] is True
        assert v["store_ops"].get("view_tx", 0) >= 1
        # columnar plane counters ride along (ISSUE 11 satellite)
        assert "store_columnar" in v
        assert v["store_columnar"]["tasks"] >= 0
        v2 = json.loads(urllib.request.urlopen(
            f"http://{srv.addr}/debug/vars").read())
        assert v2["failpoints_armed"] == [] and v2["trace_armed"] is False
    finally:
        srv.stop()


def test_debugserver_trace_endpoints():
    DebugServer = _load_debugserver().DebugServer

    srv = DebugServer("127.0.0.1:0", _stub_node())
    srv.start()
    try:
        with trace.armed():
            with trace.span("sched.tick", n=1):
                with trace.span("tick.encode"):
                    pass
            recent = json.loads(urllib.request.urlopen(
                f"http://{srv.addr}/debug/trace/recent").read())
            assert recent["armed"] is True
            names = {t["name"] for t in recent["traces"]}
            assert "sched.tick" in names
            (tick,) = [t for t in recent["traces"]
                       if t["name"] == "sched.tick"]
            assert [c["name"] for c in tick["children"]] == ["tick.encode"]
        # disarmed: the windowed endpoint arms temporarily and disarms
        win = json.loads(urllib.request.urlopen(
            f"http://{srv.addr}/debug/trace?seconds=0.05").read())
        assert win["armed"] is False and win["traces"] == []
        assert not trace.active()
        recent = json.loads(urllib.request.urlopen(
            f"http://{srv.addr}/debug/trace/recent").read())
        assert recent["armed"] is False and recent["traces"] == []
    finally:
        srv.stop()
