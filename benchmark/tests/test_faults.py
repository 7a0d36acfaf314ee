"""`correct` comes out false when the timed path is broken underneath, once
per fault a placement cell can have, and for the control placement."""
import pytest
import tiny

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
