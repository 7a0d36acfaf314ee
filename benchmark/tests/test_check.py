"""The reference check on hand-made placements."""
import check


def _nodes(n=6):
    return {f"n{i}": {"labels": {"zone": "ab"[i % 2], "disk": "x",
                                 "rack": f"r{i % 3}"},
                      "ready": True, "active": True,
                      "cpu": 10, "mem": 10}
            for i in range(n)}


def _spec(replicas, constraints=(), prefs=(), cpu=0, mem=0):
    return {"replicas": replicas, "cpu": cpu, "mem": mem,
            "constraints": [list(c) for c in constraints],
            "prefs": list(prefs)}


def _tasks(sid, nodes):
    return [(f"{sid}-{i}", sid, nid, 192) for i, nid in enumerate(nodes)]


def test_balanced_placement_reads_zero():
    nodes = _nodes()
    svcs = {"s": _spec(6, prefs=["zone"]),
            "t": _spec(2, constraints=[("zone", "==", "a")])}
    tasks = _tasks("s", list(nodes)) + _tasks("t", ["n0", "n2"])
    counted = {t[0]: t[2] for t in tasks}
    out = check.check(nodes, svcs, tasks, counted, {"s", "t"})
    assert out == dict.fromkeys(check.LIMITS, 0)
    assert check.verdict(out)


def test_each_fault_reads_above_its_limit():
    nodes = _nodes()
    svcs = {"s": _spec(4, prefs=["zone"], cpu=6),
            "t": _spec(2, constraints=[("zone", "==", "a")]),
            "g": _spec(1, mem=11)}
    tasks = (_tasks("s", ["n0", "n2", "n4", "n4"])   # zone a 4, b 0; n4 cpu 12
             + _tasks("t", ["n1"])                    # zone b; one missing
             + _tasks("g", ["n3"])                    # n3 memory 11
             + [("x-0", "x", "manager", 192)])        # off the fleet
    counted = {t[0]: t[2] for t in tasks}
    counted["t-0"] = "n0"                             # read back elsewhere
    svcs["x"] = _spec(1)
    out = check.check(nodes, svcs, tasks, counted, {"s", "t", "g"})
    assert out["unplaced"] == 1
    assert out["unreadable"] == 1
    assert out["off_fleet"] == 1
    assert out["constraint_breaks"] == 1
    assert out["over_capacity"] == 2                  # n4 cpu, n3 memory
    assert out["spread_excess"] == 3                  # zones 4 vs 0
    assert not check.verdict(out)


def test_leaf_balance_counts_uncovered_nodes_as_zero():
    nodes = _nodes()
    svcs = {"s": _spec(3)}
    tasks = _tasks("s", ["n0", "n0", "n0"])
    out = check.check(nodes, svcs, tasks, {}, {"s"})
    assert out["spread_excess"] == 2
