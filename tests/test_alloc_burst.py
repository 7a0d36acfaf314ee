"""The allocator coalesces a burst's NEW tasks: every NEW-task event of
a drained burst is queued, and the queue moves to PENDING in one store
transaction per MAX_CHANGES_PER_TRANSACTION tasks — before the next
event that writes, or at the burst's end (flush_events). The end state
must equal one `_allocate_tasks([id])` per event, in event order.
"""
import threading

import pytest

from swarmkit_tpu.allocator.allocator import Allocator
from swarmkit_tpu.api.objects import Network, Service, Task
from swarmkit_tpu.api.specs import (
    Annotations,
    NetworkAttachmentConfig,
    NetworkSpec,
    PortConfig,
    ServiceSpec,
)
from swarmkit_tpu.api.types import TaskState
from swarmkit_tpu.raft.proposer import LeadershipLost
from swarmkit_tpu.store.memory import (
    MAX_CHANGES_PER_TRANSACTION,
    MemoryStore,
    StoreAction,
)
from swarmkit_tpu.utils import failpoints, trace
from swarmkit_tpu.utils.metrics import histogram

from test_scheduler import wait_for  # noqa: E402


class CountingProposer:
    """Commits every proposal at once and keeps its changelist."""

    def __init__(self):
        self.proposals: list[list[StoreAction]] = []
        self.fail_task_updates: BaseException | None = None

    def propose_value(self, actions, commit_cb):
        if self.fail_task_updates is not None and any(
                a.kind == StoreAction.UPDATE and isinstance(a.obj, Task)
                for a in actions):
            raise self.fail_task_updates
        self.proposals.append(list(actions))
        commit_cb()

    def task_update_txs(self) -> list[int]:
        """Tasks written per proposal that updates tasks."""
        out = []
        for actions in self.proposals:
            n = sum(1 for a in actions
                    if a.kind == StoreAction.UPDATE and isinstance(a.obj, Task))
            if n:
                out.append(n)
        return out


# ------------------------------------------------------------ fixtures
KINDS = ("plain", "network", "ports")


def _seed(store, kind):
    def seed(tx):
        if kind == "network":
            tx.create(Network(id="net1", spec=NetworkSpec(
                annotations=Annotations(name="backend"),
                ipam={"subnet": "10.70.0.0/22"})))
        if kind == "ports":
            tx.create(Network(id="ingress", spec=NetworkSpec(
                annotations=Annotations(name="ingress"), ingress=True,
                ipam={"subnet": "10.71.0.0/22"})))
        s = Service(id="svc1", spec=ServiceSpec(
            annotations=Annotations(name="svc1"), replicas=1))
        if kind == "network":
            s.spec.task.networks = [NetworkAttachmentConfig(target="net1")]
        if kind == "ports":
            s.spec.endpoint.ports = [PortConfig(protocol="tcp",
                                                target_port=80)]
        tx.create(s)
    store.update(seed)


def _new_task(tid, service_id="svc1", slot=1):
    t = Task(id=tid, service_id=service_id, slot=slot)
    t.status.state = TaskState.NEW
    t.desired_state = TaskState.RUNNING
    return t


def _create_tasks(store, ids, service_id="svc1"):
    def cb(tx):
        for i, tid in enumerate(ids):
            tx.create(_new_task(tid, service_id, i + 1))
    store.update(cb)


def _image(store):
    out = {}
    for t in store.view(lambda tx: tx.find_tasks()):
        ports = tuple(p.published_port for p in t.endpoint.ports) \
            if t.endpoint else ()
        out[t.id] = (int(t.status.state), ports,
                     tuple((a["network_id"], tuple(a["addresses"]))
                           for a in t.networks
                           if isinstance(a, dict) and a.get("network_id")))
    return out


def _pools(a):
    return {nid: (set(p.allocated), p._cursor)
            for nid, p in a.ipam._pools.items()}


def _ports(a):
    return dict(a.ports._allocated), a.ports._next_dynamic


def _end_state(store, a):
    return _image(store), _pools(a), _ports(a)


def _per_event_reference(kind, ids):
    """One scalar `_allocate_tasks([id])` per NEW-task event."""
    store = MemoryStore()
    _seed(store, kind)
    a = Allocator(store)
    a.on_start(store.view(a.setup))
    _create_tasks(store, ids)
    for tid in ids:
        a._allocate_tasks([tid])
    return _end_state(store, a)


def _await_idle(a, timeout=10.0):
    """Block until the loop found its channel empty (an idle() pass)."""
    ran = threading.Event()
    orig = a.idle

    def idle():
        ran.set()
        orig()
    a.idle = idle
    assert ran.wait(timeout), "allocator loop never went idle"
    a.idle = orig


def _all_pending(store, n):
    ts = store.view(lambda tx: tx.find_tasks())
    return len(ts) == n and all(t.status.state == TaskState.PENDING
                                for t in ts)


# ------------------------------------------------ one burst, few writes
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [1, 7, 200, 256])
def test_burst_moves_new_tasks_in_one_tx_per_200(kind, n):
    """N NEW-task events in one drained burst (N <= MAX_DRAIN) cost
    ceil(N / 200) write transactions, not N, and land the same state
    as one allocation per event."""
    store = MemoryStore(proposer=CountingProposer())
    _seed(store, kind)
    a = Allocator(store)
    a.start()
    try:
        _await_idle(a)
        assert store.proposer.task_update_txs() == []
        ids = [f"t{i:04d}" for i in range(n)]
        _create_tasks(store, ids)
        assert wait_for(lambda: _all_pending(store, n), timeout=10)
    finally:
        a.stop()
    sizes = store.proposer.task_update_txs()
    assert len(sizes) == -(-n // MAX_CHANGES_PER_TRANSACTION), sizes
    assert sum(sizes) == n
    assert _end_state(store, a) == _per_event_reference(kind, ids)


def test_burst_histogram_and_span_attr():
    """swarm_allocator_batch_tasks observes each transaction's task
    count; the burst's loop.allocator span carries tasks=."""
    h = histogram("swarm_allocator_batch_tasks")
    _, sum0, n0 = h.snapshot()
    store = MemoryStore()
    _seed(store, "network")
    a = Allocator(store)
    with trace.armed() as rec:
        a.start()
        try:
            _await_idle(a)
            _create_tasks(store, [f"t{i:04d}" for i in range(230)])
            assert wait_for(lambda: _all_pending(store, 230), timeout=10)
        finally:
            a.stop()
        spans = [r for r in rec.snapshot() if r["name"] == "loop.allocator"]
    _, sum1, n1 = h.snapshot()
    assert n1 - n0 == 2 and sum1 - sum0 == 230
    assert h.buckets[-1] == MAX_CHANGES_PER_TRANSACTION
    assert sum(r["attrs"].get("tasks", 0) for r in spans) == 230
    assert max(r["attrs"].get("tasks", 0) for r in spans) == 230


# ------------------------------------------------------- order is kept
def _drive_burst(per_event: bool):
    """Store writes that land while the loop is busy, then the loop's
    burst over their events: coalesced with one flush at the end
    (per_event=False), or flushed after every event (the reference)."""
    store = MemoryStore()

    def seed(tx):
        tx.create(Network(id="netA", spec=NetworkSpec(
            annotations=Annotations(name="netA"),
            ipam={"subnet": "10.73.0.0/24"})))
        s = Service(id="plain", spec=ServiceSpec(
            annotations=Annotations(name="plain"), replicas=1))
        s.spec.task.networks = [NetworkAttachmentConfig(target="netA")]
        tx.create(s)
        s = Service(id="late", spec=ServiceSpec(
            annotations=Annotations(name="late"), replicas=1))
        s.spec.task.networks = [NetworkAttachmentConfig(target="netL")]
        tx.create(s)
    store.update(seed)
    a = Allocator(store)
    a.on_start(store.view(a.setup))
    _, ch = store.view_and_watch(None, limit=None)
    for cb in (
            # ids against event order: a sweep of NEW tasks (sorted by
            # id) would hand out netA's addresses in another order
            lambda tx: [tx.create(_new_task(f"p{i}", "plain", i + 1))
                        for i in reversed(range(5))],
            # a task of the service whose network does not exist yet
            lambda tx: tx.create(_new_task("early", "late", 1)),
            lambda tx: tx.create(Network(id="netL", spec=NetworkSpec(
                annotations=Annotations(name="netL"),
                ipam={"subnet": "10.72.0.0/24"}))),
            lambda tx: [tx.create(_new_task(f"l{i}", "late", i + 2))
                        for i in range(6)],
            lambda tx: tx.create(_new_task("doomed", "late", 9)),
            lambda tx: tx.delete(Task, "doomed"),
            lambda tx: [tx.create(_new_task(f"q{i}", "plain", i + 6))
                        for i in range(3)]):
        store.update(cb)
    events = ch.drain()
    store.queue.stop_watch(ch)
    assert len(events) < Allocator.MAX_DRAIN
    for ev in events:
        a.handle(ev)
        if per_event:
            a.flush_events()
    a.flush_events()
    return store, a


def test_burst_keeps_write_order():
    """NEW tasks, a network create, more NEW tasks on that network and a
    task deleted in the same burst: the coalesced burst lands exactly
    the per-event end state; the deleted task is never allocated."""
    store, a = _drive_burst(per_event=False)
    ref_store, ref = _drive_burst(per_event=True)
    state = _end_state(store, a)
    assert state == _end_state(ref_store, ref)
    image = state[0]
    assert "doomed" not in image
    assert all(s == int(TaskState.PENDING) for s, _, _ in image.values())
    late = [v for k, v in image.items() if k == "early" or k[0] == "l"]
    assert len(late) == 7 and all(v[2] and v[2][0][0] == "netL"
                                  for v in late)
    # the gateway, the service VIP and seven task addresses
    assert len(_pools(a)["netL"][0]) == 9


def test_task_deleted_mid_burst_is_not_allocated():
    """The loop queues a NEW task, then handles its delete: the queue
    flushes first and finds the task gone, so nothing is written for
    it and no address is taken."""
    store = MemoryStore(proposer=CountingProposer())
    _seed(store, "network")
    a = Allocator(store)
    a.on_start(store.view(a.setup))
    pools = _pools(a)
    _, ch = store.view_and_watch(None, limit=None)
    _create_tasks(store, ["doomed"])
    for ev in ch.drain():
        a.handle(ev)
    assert list(a._new_tasks) == ["doomed"]
    store.update(lambda tx: tx.delete(Task, "doomed"))
    for ev in ch.drain():
        a.handle(ev)
    a.flush_events()
    store.queue.stop_watch(ch)
    assert not a._new_tasks
    assert store.proposer.task_update_txs() == []
    assert _pools(a) == pools


# ------------------------------------------------------- crash mid-burst
def test_crashed_burst_requeues_and_idle_retries():
    """alloc.batch.commit fires once: the burst's ids go back on the
    queue, idle() moves every task to PENDING, no address leaks."""
    store = MemoryStore()
    _seed(store, "network")
    a = Allocator(store)
    a.on_start(store.view(a.setup))
    _, ch = store.view_and_watch(None, limit=None)
    ids = [f"t{i:04d}" for i in range(30)]
    _create_tasks(store, ids)
    for ev in ch.drain():
        a.handle(ev)
    with failpoints.armed("alloc.batch.commit",
                          error=RuntimeError("burst crash"), times=1):
        with pytest.raises(RuntimeError):
            a.flush_events()
    assert list(a._new_tasks) == ids
    assert not any(s == int(TaskState.PENDING)
                   for s, _, _ in _image(store).values())
    a.idle()
    assert not a._new_tasks
    assert _all_pending(store, 30)
    store.queue.stop_watch(ch)
    addrs = [x for _, _, att in _image(store).values()
             for _, xs in att for x in xs]
    assert len(addrs) == len(set(addrs)) == 30
    fresh = Allocator(store)
    fresh.on_start(store.view(fresh.setup))
    assert _pools(fresh)["net1"][0] == _pools(a)["net1"][0]


def test_crashed_flush_before_event_still_handles_the_event():
    """A queued burst that crashes when the next writing event arrives
    is logged and re-queued; that event is still handled, and the
    burst's end retries the queue."""
    store = MemoryStore()
    _seed(store, "network")
    a = Allocator(store)
    a.on_start(store.view(a.setup))
    _, ch = store.view_and_watch(None, limit=None)
    _create_tasks(store, [f"t{i}" for i in range(4)])

    def mk_svc(tx):
        s = Service(id="svc2", spec=ServiceSpec(
            annotations=Annotations(name="svc2"), replicas=1))
        s.spec.task.networks = [NetworkAttachmentConfig(target="net1")]
        tx.create(s)
    store.update(mk_svc)
    with failpoints.armed("alloc.batch.commit",
                          error=RuntimeError("burst crash"), times=1):
        for ev in ch.drain():
            a.handle(ev)
    svc2 = store.view(lambda tx: tx.get_service("svc2"))
    assert svc2.endpoint and svc2.endpoint.get("virtual_ips")
    assert len(a._new_tasks) == 4
    a.flush_events()
    store.queue.stop_watch(ch)
    assert _all_pending(store, 4)


def test_leadership_lost_stops_the_loop():
    """LeadershipLost from the burst's proposal still ends the loop."""
    store = MemoryStore(proposer=CountingProposer())
    _seed(store, "plain")
    a = Allocator(store)
    a.start()
    try:
        _await_idle(a)
        store.proposer.fail_task_updates = LeadershipLost("demoted")
        _create_tasks(store, [f"t{i}" for i in range(12)])
        assert wait_for(lambda: not a._thread.is_alive(), timeout=10)
    finally:
        a.stop()
    assert store.proposer.task_update_txs() == []
