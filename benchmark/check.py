"""The plain reference that decides `correct`.

It imports nothing of the program. It takes the fleet as seeded, the specs
of the services the run created, the tasks as read back from the leader's
raft-committed store once the window has closed, and the collector's
ASSIGNED sightings, all as plain Python values, and works out from the
scheduler's semantics (reference: manager/scheduler filters and the spread
strategy) the numbers below. Each has a limit; a run is correct when every
number is at or under its limit.

  unplaced         replicas of a due service that are not ASSIGNED to a node
                   in the read-back store (a task never placed, or lost)
  surplus          tasks of a service beyond its replica count
  unreadable       tasks the collector counted as ASSIGNED to a node that the
                   store does not read back as ASSIGNED to that same node
  off_fleet        tasks on a node that is not a seeded READY, ACTIVE worker
                   (the drained manager node included)
  constraint_breaks  tasks on a node whose labels break their constraints
  over_capacity    nodes whose tasks reserve more cpu or memory than the node
                   has
  spread_excess    the largest imbalance of a service's tasks beyond one, at
                   any level of its spread tree: between the branches (label
                   values) under one parent, and, at the leaves, between the
                   eligible nodes of one branch

The spread strategy fills the node or branch with the fewest of a service's
tasks first, so with capacity to spare (the configuration holds about four
times what a run places) counts under one parent differ by at most one.
Every number has the limit 0: the counts are exact comparisons, and
spread_excess's limit is the configuration's own guarantee. PERF.md gives
the readings of sound runs and of the control (benchmark/control.py).
"""
from __future__ import annotations

from collections import Counter, defaultdict

LIMITS = {
    "unplaced": 0,
    "surplus": 0,
    "unreadable": 0,
    "off_fleet": 0,
    "constraint_breaks": 0,
    "over_capacity": 0,
    "spread_excess": 0,
}

# task states as the program numbers them (api/types.proto TaskState)
ASSIGNED, COMPLETE = 192, 576


def _matches(labels: dict, constraints: list) -> bool:
    for key, op, value in constraints:
        have = labels.get(key)
        if op == "==":
            if have != value:
                return False
        elif op == "!=":
            if have == value:
                return False
        else:
            raise ValueError(f"constraint operator {op!r} is not modelled")
    return True


class _Trees:
    """Eligible nodes and spread-tree shapes, built once per kind of
    service (its constraints and preference labels)."""

    def __init__(self, nodes: dict):
        self._nodes = nodes
        self._cache = {}

    def get(self, spec: dict):
        key = (tuple(map(tuple, spec["constraints"])), tuple(spec["prefs"]))
        tree = self._cache.get(key)
        if tree is None:
            tree = self._cache[key] = self._build(spec)
        return tree

    def _build(self, spec: dict):
        prefs = spec["prefs"]
        path = {}
        children = defaultdict(set)      # parent path -> child label values
        leaf_size = Counter()            # full path -> eligible node count
        for nid, n in self._nodes.items():
            if not (n["ready"] and n["active"]
                    and _matches(n["labels"], spec["constraints"])):
                continue
            p = tuple(n["labels"].get(k) for k in prefs)
            path[nid] = p
            for lvl in range(len(prefs)):
                children[p[:lvl]].add(p[lvl])
            leaf_size[p] += 1
        return path, children, leaf_size


def _tree_excess(tree, placed: list) -> int:
    """Largest (max - min - 1) of one service's task counts over sibling
    branches under each parent of its spread tree, and over the eligible
    nodes of each leaf branch."""
    path, children, leaf_size = tree
    per_node = Counter(nid for nid in placed if nid in path)
    at = Counter()
    for nid, c in per_node.items():
        p = path[nid]
        for lvl in range(len(p) + 1):
            at[p[:lvl]] += c
    worst = -1
    for parent, values in children.items():
        counts = [at.get(parent + (v,), 0) for v in values]
        worst = max(worst, max(counts) - min(counts) - 1)
    by_leaf = defaultdict(list)
    for nid, c in per_node.items():
        by_leaf[path[nid]].append(c)
    for p, counts in by_leaf.items():
        low = min(counts) if len(counts) == leaf_size[p] else 0
        worst = max(worst, max(counts) - low - 1)
    return worst


def check(nodes: dict, services: dict, tasks: list, counted: dict,
          due: set) -> dict:
    """nodes: id -> {labels, ready, active, cpu, mem} (seeded workers);
    services: id -> plain spec {replicas, cpu, mem, constraints, prefs}
    for every service that has tasks; tasks: (id, service_id, node_id,
    state) for every task read back; counted: task id -> node id as the
    collector saw it ASSIGNED; due: ids of the services whose every replica
    must be placed. Returns the numbers named in LIMITS."""
    live = {}
    by_service = defaultdict(list)
    for tid, sid, nid, state in tasks:
        if ASSIGNED <= state < COMPLETE and nid:
            live[tid] = (sid, nid)
            by_service[sid].append(nid)

    out = dict.fromkeys(LIMITS, 0)
    for sid in due:
        have = len(by_service.get(sid, ()))
        want = services[sid]["replicas"]
        out["unplaced"] += max(0, want - have)
    for sid, placed in by_service.items():
        if sid in services:
            out["surplus"] += max(0, len(placed) - services[sid]["replicas"])
    out["unreadable"] = sum(1 for tid, nid in counted.items()
                            if live.get(tid, (None, None))[1] != nid)

    used = defaultdict(lambda: [0, 0])
    for tid, (sid, nid) in live.items():
        spec = services.get(sid)
        node = nodes.get(nid)
        if node is None or not (node["ready"] and node["active"]):
            out["off_fleet"] += 1
            continue
        if spec is None:
            continue
        if not _matches(node["labels"], spec["constraints"]):
            out["constraint_breaks"] += 1
        u = used[nid]
        u[0] += spec["cpu"]
        u[1] += spec["mem"]
    for nid, (cpu, mem) in used.items():
        n = nodes[nid]
        if cpu > n["cpu"] or mem > n["mem"]:
            out["over_capacity"] += 1

    trees = _Trees(nodes)
    worst = 0
    for sid in due:
        placed = by_service.get(sid)
        if placed:
            worst = max(worst, _tree_excess(trees.get(services[sid]), placed))
    out["spread_excess"] = worst
    return out


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
