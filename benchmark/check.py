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

Where the configuration declares networks, or kinds with networks, an
endpoint mode or ports, `check_network` adds five numbers, each with the
limit 0, from the networks, node attachments, service endpoints and task
attachments as read back. "Live" is what it is above: ASSIGNED up to
COMPLETE. A service attaches to the networks its kind names and, where it
publishes a port in ingress mode, to the ingress network as well.

  unattached       live tasks that do not hold exactly one address on each
                   network their service attaches to, or that hold one on
                   any other network (reference allocator/network.go: a
                   task is allocated once it has an attachment, with one
                   address, on every network of its spec and on ingress
                   when its service publishes through the routing mesh)
  address_conflicts  addresses held twice on one network, counting every
                   task below COMPLETE (a task holds its addresses from
                   its move to PENDING, when the allocator grants them,
                   until it ends), service VIPs, node attachments and the
                   gateway (network.go: one IPAM pool per network; VIPs,
                   task and node attachments all draw from it, the gateway
                   is held from the start), and addresses outside the
                   network's subnet (the declared one, where the
                   configuration declares it) or on its network or
                   broadcast address
  vip_missing      due services in VIP mode without exactly one VIP on each
                   network they attach to and none elsewhere (network.go
                   allocateVIP), and due services in DNSRR mode holding
                   any VIP (a DNSRR endpoint has none)
  port_conflicts   an ingress-mode published port held by two services (the
                   routing mesh binds it on every node: cluster-unique); a
                   dynamically assigned ingress port outside 30000-32767
                   (portallocator.go's dynamic range); a fixed published
                   port that the service's endpoint does not hold as asked;
                   a live task whose endpoint ports differ from its
                   service's allocated ones (the allocator copies them onto
                   every task)
  host_port_conflicts  pairs of live tasks on one node that publish the same
                   (protocol, port) in host mode (the scheduler's
                   HostPortFilter: a host-mode port binds the node's own
                   port, so one task per node may hold it)
"""
from __future__ import annotations

import ipaddress
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

NET_LIMITS = {
    "unattached": 0,
    "address_conflicts": 0,
    "vip_missing": 0,
    "port_conflicts": 0,
    "host_port_conflicts": 0,
}

# task states as the program numbers them (api/types.proto TaskState)
ASSIGNED, COMPLETE = 192, 576
DYNAMIC_PORTS = range(30000, 32768)


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


def _live(tasks: list) -> dict:
    """task id -> (service id, node id) of the live tasks."""
    return {tid: (sid, nid) for tid, sid, nid, state in tasks
            if ASSIGNED <= state < COMPLETE and nid}


def check(nodes: dict, services: dict, tasks: list, counted: dict,
          due: set) -> dict:
    """nodes: id -> {labels, ready, active, cpu, mem} (seeded workers);
    services: id -> plain spec {replicas, cpu, mem, constraints, prefs}
    for every service that has tasks; tasks: (id, service_id, node_id,
    state) for every task read back; counted: task id -> node id as the
    collector saw it ASSIGNED; due: ids of the services whose every replica
    must be placed. Returns the numbers named in LIMITS."""
    live = _live(tasks)
    by_service = defaultdict(list)
    for sid, nid in live.values():
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


def _address(addr: str):
    """An address as held: plain (10.0.0.5) or with its prefix
    (10.0.0.5/16)."""
    return ipaddress.ip_interface(addr).ip


def check_network(services: dict, tasks: list, due: set, net: dict) -> dict:
    """services, tasks, due: as `check` takes them, each plain spec with the
    kind's `networks` (names), `endpoint_mode` and `ports` ({target,
    published, mode, protocol}) where it has them. net, as read back:
      networks  id -> {name, subnet, ingress}
      declared  name -> subnet, as the configuration declares it
      nodes     node id -> [(network id, address)], its attachments
      services  id -> {virtual_ips: [(network id, address)],
                       ports: [(protocol, target, published, mode)]}
      tasks     id -> {attachments: [(network id, address)],
                       ports: [(protocol, target, published, mode)]}
    Returns the numbers named in NET_LIMITS."""
    nets = net["networks"]
    by_name = {n["name"]: nid for nid, n in nets.items()}
    ingress = next((nid for nid, n in nets.items() if n["ingress"]), None)

    def attached(spec: dict) -> set:
        # a name the store does not hold resolves to itself: nothing is
        # attached to it, so every task of the service counts
        want = {by_name.get(name, name) for name in spec.get("networks", ())}
        if any(p["mode"] == "ingress" for p in spec.get("ports", ())):
            want.add(ingress)
        return want

    live = _live(tasks)
    out = dict.fromkeys(NET_LIMITS, 0)
    served = {sid: [tuple(p) for p in ep.get("ports") or ()]
              for sid, ep in net["services"].items()}

    # ---- attachments of live tasks
    for tid, (sid, _) in live.items():
        spec = services.get(sid)
        if spec is None:
            continue
        held = Counter(nid for nid, _ in net["tasks"][tid]["attachments"])
        want = attached(spec)
        if set(held) != want or any(c != 1 for c in held.values()):
            out["unattached"] += 1

    # ---- addresses, per network; a PENDING task holds its addresses too
    holders = defaultdict(list)         # network id -> addresses held
    for tid, _, _, state in tasks:
        if state < COMPLETE:
            for nid, addr in net["tasks"][tid]["attachments"]:
                holders[nid].append(addr)
    for ep in net["services"].values():
        for nid, addr in ep.get("virtual_ips") or ():
            holders[nid].append(addr)
    for atts in net["nodes"].values():
        for nid, addr in atts:
            holders[nid].append(addr)
    for nid, n in nets.items():
        subnet = ipaddress.ip_network(
            net["declared"].get(n["name"], n["subnet"]), strict=False)
        gateway = subnet.network_address + 1
        seen = Counter(_address(a) for a in holders.get(nid, ()))
        seen[gateway] += 1
        out["address_conflicts"] += sum(c - 1 for c in seen.values())
        out["address_conflicts"] += sum(
            c for a, c in seen.items()
            if a not in subnet or a in (subnet.network_address,
                                        subnet.broadcast_address))

    # ---- VIPs of due services
    for sid in due:
        spec = services[sid]
        vips = Counter(nid for nid, _ in
                       net["services"].get(sid, {}).get("virtual_ips") or ())
        if spec.get("endpoint_mode", "vip") == "dnsrr":
            bad = bool(vips)
        else:
            bad = set(vips) != attached(spec) \
                or any(c != 1 for c in vips.values())
        out["vip_missing"] += bad

    # ---- published ports
    ingress_owners = Counter()
    for sid, ports in served.items():
        for proto, _, published, mode in set(ports):
            if mode == "ingress" and published:
                ingress_owners[(proto, published)] += 1
    out["port_conflicts"] += sum(c - 1 for c in ingress_owners.values())
    for sid in due:
        asked = services[sid].get("ports", ())
        held = served.get(sid, [])
        if len(held) != len(asked):
            out["port_conflicts"] += max(len(held), len(asked))
            continue
        for p, (proto, target, published, mode) in zip(asked, held):
            if (proto, target, mode) != (p["protocol"], p["target"],
                                         p["mode"]):
                out["port_conflicts"] += 1
            elif p["published"]:
                out["port_conflicts"] += published != p["published"]
            elif mode == "ingress":
                out["port_conflicts"] += published not in DYNAMIC_PORTS
    for tid, (sid, _) in live.items():
        if sid in services:
            got = [tuple(p) for p in net["tasks"][tid]["ports"]]
            out["port_conflicts"] += got != served.get(sid, [])

    # ---- host-mode ports, per node
    bound = Counter()
    for tid, (sid, node) in live.items():
        if sid not in services:
            continue
        for proto, _, published, mode in net["tasks"][tid]["ports"]:
            if mode == "host" and published:
                bound[(node, proto, published)] += 1
    out["host_port_conflicts"] = sum(c * (c - 1) // 2
                                     for c in bound.values())
    return out


def room(nodes: dict, services: dict, tasks: list) -> dict:
    """Reserved against held cpu and memory over the READY, ACTIVE seeded
    workers, from the live tasks: how close a run came to filling the
    fleet."""
    fleet = {nid for nid, n in nodes.items() if n["ready"] and n["active"]}
    out = {"cpu": [0, sum(nodes[n]["cpu"] for n in fleet)],
           "mem": [0, sum(nodes[n]["mem"] for n in fleet)]}
    for sid, nid in _live(tasks).values():
        spec = services.get(sid)
        if spec is not None and nid in fleet:
            out["cpu"][0] += spec["cpu"]
            out["mem"][0] += spec["mem"]
    return out


def verdict(numbers: dict) -> bool:
    """Every number at or under its limit; the network numbers count
    where they were computed."""
    return all(numbers[k] <= lim for k, lim in LIMITS.items()) and all(
        numbers[k] <= lim for k, lim in NET_LIMITS.items() if k in numbers)
