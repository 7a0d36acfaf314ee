"""`correct` comes out false when the timed path is broken underneath, once
per fault a placement cell can have, once per fault of the allocator's
addresses and ports and of the scheduler's host-port mask, and for the
control placement."""
import pytest
import tiny

from swarmkit_tpu.allocator import batched
from swarmkit_tpu.allocator.allocator import DYNAMIC_PORT_START, Allocator
from swarmkit_tpu.scheduler.encode import IncrementalEncoder
from swarmkit_tpu.store import memory
from swarmkit_tpu.store.memory import ASSIGN_OK

import control

_orig = memory.MemoryStore.assign_wave


def _fake_ok(store, assignments):
    """Report rows as committed without writing them."""
    tasks = []
    for tid, nid in assignments:
        t = store.view().get_task(tid).copy()
        t.node_id = nid
        tasks.append(t)
    return [ASSIGN_OK] * len(assignments), tasks


def unchanged(self, assignments, **kw):
    """The wave returns the store unchanged."""
    return _fake_ok(self, assignments)


def half_left_out(self, assignments, **kw):
    """Half of each wave is never written."""
    k = len(assignments) // 2
    codes, tasks = _orig(self, assignments[:k], **kw)
    c2, t2 = _fake_ok(self, assignments[k:])
    return codes + c2, tasks + t2


def altered(self, assignments, **kw):
    """Each answer moved to the next node (another zone) where it is
    produced."""
    def nxt(nid):
        head, num = nid.rsplit("-", 1)
        return f"{head}-{(int(num) + 1) % 64:0{len(num)}d}"
    return _orig(self, [(t, nxt(n)) for t, n in assignments], **kw)


def piled(self, assignments, **kw):
    """Every answer put on the first node, whatever its room."""
    return _orig(self, [(t, "node-000000") for t, _ in assignments], **kw)


CELLS = [w["name"] for w in tiny.harness.load_json("BENCHMARK.json")
         ["workloads"]]


@pytest.mark.parametrize("fault, reads", [
    (unchanged, "unplaced"),
    (half_left_out, "unplaced"),
    (altered, "constraint_breaks"),
    (piled, "over_capacity"),
])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_run_incorrect(cell, fault, reads, monkeypatch):
    monkeypatch.setattr(memory.MemoryStore, "assign_wave", fault)
    r = tiny.run(cell, seconds=2.0)
    assert r["correct"] is False
    assert r["checks"][reads]["value"] > r["checks"][reads]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cell):
    r = tiny.run(cell, control=control.random_placer)
    assert r["correct"], r["checks"]
    assert r["control_correct"] is False
    assert r["control"]["constraint_breaks"] > 0


# --------------------------------------------- networks, VIPs and ports
_grant_many = batched.BatchedIPAM.allocate_many
_chunk = Allocator._alloc_chunk_in_tx
_service = Allocator._allocate_service
_encode = IncrementalEncoder.encode


def _one_network_short(alloc):
    """Shadow the allocator's network resolution with one that leaves each
    service's last network out."""
    full = alloc._service_networks

    def short(tx, service):
        nets = full(tx, service)
        return nets if nets is None else nets[:-1]

    alloc._service_networks = short


def address_twice(monkeypatch):
    """IPAM hands the first address of every bulk grant out twice."""
    def twice(self, net_id, k):
        addrs = _grant_many(self, net_id, k)
        return addrs[:1] + addrs[:-1]
    monkeypatch.setattr(batched.BatchedIPAM, "allocate_many", twice)


def attachment_dropped(monkeypatch):
    """Tasks moved to PENDING in bulk miss one network's attachment."""
    def dropped(self, *a):
        _one_network_short(self)
        try:
            return _chunk(self, *a)
        finally:
            del self._service_networks
    monkeypatch.setattr(Allocator, "_alloc_chunk_in_tx", dropped)


def vip_dropped(monkeypatch):
    """Services get no VIP on their last network."""
    def dropped(self, service_id):
        _one_network_short(self)
        try:
            return _service(self, service_id)
        finally:
            del self._service_networks
    monkeypatch.setattr(Allocator, "_allocate_service", dropped)


def port_regranted(monkeypatch):
    """The port allocator grants every dynamic ingress port as 30000, held
    or not."""
    monkeypatch.setattr(batched.BatchedPorts, "_grant_dynamic_run",
                        lambda self, protocol, k: [DYNAMIC_PORT_START] * k)


def port_mask_clear(monkeypatch):
    """The encoder leaves every group's host ports out of the kernel's
    port mask."""
    def clear(self, *a, **kw):
        p = _encode(self, *a, **kw)
        p.has_ports[:] = False
        p.group_ports[:] = False
        return p
    monkeypatch.setattr(IncrementalEncoder, "encode", clear)


def crowded_host_ports() -> dict:
    """The network definition with its host-port kind held to moon-1's 22
    nodes and every service asking for 23 to 30 replicas. The allocator
    gives a host-mode port to one service alone, whose replicas spread one
    to a node while it has nodes to spare: only where a service asks for
    more replicas than it has eligible nodes does the port mask decide
    anything (the sound program leaves the rest pending)."""
    d = tiny.network_definition()
    d["config"]["service_kinds"][1]["constraints"] = [["zone", "==",
                                                       "moon-1"]]
    d["traffic"]["sizes"].update(min=23, max=30)
    return d


def stack_networks() -> dict:
    """The network definition without a host port: the program is correct
    on it, so a fault alone moves its numbers."""
    return tiny.network_definition(host_port=False)


@pytest.mark.parametrize("fault, reads, definition", [
    (address_twice, "address_conflicts", stack_networks),
    (attachment_dropped, "unattached", stack_networks),
    (vip_dropped, "vip_missing", stack_networks),
    (port_regranted, "port_conflicts", stack_networks),
    (port_mask_clear, "host_port_conflicts", crowded_host_ports),
])
def test_network_fault_makes_run_incorrect(fault, reads, definition,
                                           monkeypatch):
    fault(monkeypatch)
    r = tiny.run("fleet1k.rollout", seconds=2.0, definition_=definition())
    assert r["correct"] is False
    assert r["checks"][reads]["value"] > r["checks"][reads]["limit"]


def test_control_breaks_host_ports():
    """The control ignores host ports as it ignores constraints: where the
    program binds host port 8080 once a node, the control binds it twice."""
    r = tiny.run("fleet1k.rollout", control=control.random_placer,
                 definition_=crowded_host_ports())
    assert r["checks"]["host_port_conflicts"]["value"] == 0
    assert r["control_correct"] is False
    assert r["control"]["host_port_conflicts"] > 0


def test_crowded_host_ports_stay_pending_when_sound():
    """Without a fault the crowded definition binds no port twice: what
    does not fit stays pending."""
    r = tiny.run("fleet1k.rollout", seconds=2.0,
                 definition_=crowded_host_ports())
    assert r["checks"]["host_port_conflicts"]["value"] == 0
    assert r["checks"]["unplaced"]["value"] > 0
