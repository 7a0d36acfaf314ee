"""The reference check on hand-made placements."""
import check
import pytest


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


# ------------------------------------------------------------------ networks
ING, FRONT = "net-ing", "net-front"


def _net_world():
    """Two services on a /24 overlay and a /28 ingress, all sound: web (VIP,
    a dynamic ingress port) with two tasks, edge (DNSRR, host port 8080)
    with two tasks on two nodes."""
    services = {
        "web": dict(_spec(2), networks=["front"],
                    ports=[{"target": 80, "published": 0, "mode": "ingress",
                            "protocol": "tcp"}]),
        "edge": dict(_spec(2), networks=["front"], endpoint_mode="dnsrr",
                     ports=[{"target": 8080, "published": 8080,
                             "mode": "host", "protocol": "tcp"}]),
    }
    tasks = _tasks("web", ["n0", "n1"]) + _tasks("edge", ["n0", "n1"])
    web_ports = [("tcp", 80, 30000, "ingress")]
    edge_ports = [("tcp", 8080, 8080, "host")]
    net = {
        "networks": {ING: {"name": "ingress", "subnet": "10.9.0.0/28",
                           "ingress": True},
                     FRONT: {"name": "front", "subnet": "10.1.0.0/24",
                             "ingress": False}},
        "declared": {"ingress": "10.9.0.0/28"},
        "nodes": {"n0": [(ING, "10.9.0.2")], "n1": [(ING, "10.9.0.3")]},
        "services": {
            "web": {"virtual_ips": [(FRONT, "10.1.0.2"), (ING, "10.9.0.4")],
                    "ports": web_ports},
            "edge": {"virtual_ips": [], "ports": edge_ports},
        },
        "tasks": {
            "web-0": {"attachments": [(FRONT, "10.1.0.3"),
                                      (ING, "10.9.0.5")],
                      "ports": web_ports},
            "web-1": {"attachments": [(FRONT, "10.1.0.4/24"),
                                      (ING, "10.9.0.6/28")],
                      "ports": web_ports},
            "edge-0": {"attachments": [(FRONT, "10.1.0.5")],
                       "ports": edge_ports},
            "edge-1": {"attachments": [(FRONT, "10.1.0.6")],
                       "ports": edge_ports},
        },
    }
    return services, tasks, net


def _net_check(services, tasks, net):
    return check.check_network(services, tasks, set(services), net)


def test_sound_network_reads_zero():
    out = _net_check(*_net_world())
    assert out == dict.fromkeys(check.NET_LIMITS, 0)
    assert check.verdict(dict(dict.fromkeys(check.LIMITS, 0), **out))


@pytest.mark.parametrize("task, atts", [
    ("web-0", [(FRONT, "10.1.0.3")]),                          # no ingress
    ("web-0", [(FRONT, "10.1.0.3"), (FRONT, "10.1.0.9"),
               (ING, "10.9.0.5")]),                            # two on front
    ("edge-0", [(FRONT, "10.1.0.5"), (ING, "10.9.0.7")]),      # not its net
])
def test_unattached(task, atts):
    services, tasks, net = _net_world()
    net["tasks"][task]["attachments"] = atts
    assert _net_check(services, tasks, net)["unattached"] == 1


def test_unattached_counts_live_tasks_only():
    services, tasks, net = _net_world()
    net["tasks"]["web-0"]["attachments"] = []
    tasks = [t if t[0] != "web-0" else t[:3] + (64,) for t in tasks]
    assert _net_check(services, tasks, net)["unattached"] == 0


@pytest.mark.parametrize("state, reads", [(64, 1), (576, 0)])
def test_address_conflicts_count_tasks_below_complete(state, reads):
    """A PENDING task holds the addresses the allocator granted it, so one
    that a live task holds too is held twice; a task that has ended holds
    none."""
    services, tasks, net = _net_world()
    services["web"]["replicas"] = 3
    tasks.append(("web-2", "web", "", state))
    net["tasks"]["web-2"] = {"attachments": [(FRONT, "10.1.0.3"),
                                             (ING, "10.9.0.7")],
                             "ports": net["tasks"]["web-0"]["ports"]}
    assert _net_check(services, tasks, net)["address_conflicts"] == reads


@pytest.mark.parametrize("where, addr, reads", [
    (("tasks", "web-1", 0), "10.1.0.3", 1),       # a task's twice
    (("services", "web", 0), "10.1.0.5", 1),      # a VIP a task holds
    (("nodes", "n1", 0), "10.9.0.2", 1),          # two nodes' ingress
    (("tasks", "web-0", 0), "10.1.0.1", 1),       # the gateway
    (("tasks", "web-0", 0), "10.1.0.0", 1),       # the network address
    (("tasks", "web-0", 0), "10.1.0.255", 1),     # the broadcast address
    (("tasks", "web-0", 0), "10.7.0.3", 1),       # outside the subnet
    (("tasks", "web-0", 1), "10.9.0.15", 1),      # the /28's broadcast
    (("nodes", "n0", 0), "10.9.0.20", 1),         # outside the /28
])
def test_address_conflicts(where, addr, reads):
    services, tasks, net = _net_world()
    kind, key, i = where
    row = net[kind][key]
    items = row if kind == "nodes" else row[
        "attachments" if kind == "tasks" else "virtual_ips"]
    items[i] = (items[i][0], addr)
    assert _net_check(services, tasks, net)["address_conflicts"] == reads


def test_declared_subnet_rules_over_the_read_back_one():
    services, tasks, net = _net_world()
    net["networks"][ING]["subnet"] = "10.9.0.0/16"   # not as declared
    net["nodes"]["n0"] = [(ING, "10.9.1.2")]
    assert _net_check(services, tasks, net)["address_conflicts"] == 1


@pytest.mark.parametrize("service, vips, reads", [
    ("web", [(FRONT, "10.1.0.2")], 1),                         # no ingress
    ("web", [(FRONT, "10.1.0.2"), (FRONT, "10.1.0.9"),
             (ING, "10.9.0.4")], 1),                           # two on front
    ("edge", [(FRONT, "10.1.0.9")], 1),                        # DNSRR, a VIP
])
def test_vip_missing(service, vips, reads):
    services, tasks, net = _net_world()
    net["services"][service]["virtual_ips"] = vips
    assert _net_check(services, tasks, net)["vip_missing"] == reads


def test_port_conflicts():
    services, tasks, net = _net_world()
    # another service holds web's ingress port
    net["services"]["other"] = {"virtual_ips": [],
                                "ports": [("tcp", 81, 30000, "ingress")]}
    assert _net_check(services, tasks, net)["port_conflicts"] == 1
    del net["services"]["other"]
    # a dynamic port outside 30000-32767, on the service and its tasks
    bad = [("tcp", 80, 8000, "ingress")]
    net["services"]["web"]["ports"] = bad
    for t in ("web-0", "web-1"):
        net["tasks"][t]["ports"] = bad
    assert _net_check(services, tasks, net)["port_conflicts"] == 1
    # a fixed port not honoured, and a service whose ports were never
    # allocated (its tasks then differ from it too)
    services, tasks, net = _net_world()
    net["services"]["edge"]["ports"] = [("tcp", 8080, 8081, "host")]
    assert _net_check(services, tasks, net)["port_conflicts"] == 3
    net["services"]["edge"]["ports"] = []
    assert _net_check(services, tasks, net)["port_conflicts"] == 3
    # a task whose ports differ from its service's
    services, tasks, net = _net_world()
    net["tasks"]["web-1"]["ports"] = []
    assert _net_check(services, tasks, net)["port_conflicts"] == 1


def test_host_port_conflicts_count_pairs_on_a_node():
    services, tasks, net = _net_world()
    tasks = _tasks("web", ["n0", "n1"]) + _tasks("edge", ["n0", "n0"])
    assert _net_check(services, tasks, net)["host_port_conflicts"] == 1
    services["edge"]["replicas"] = 3
    tasks.append(("edge-2", "edge", "n0", 192))
    net["tasks"]["edge-2"] = dict(net["tasks"]["edge-1"])
    assert _net_check(services, tasks, net)["host_port_conflicts"] == 3
    # the same port over another protocol is another port
    net["tasks"]["edge-2"] = {"attachments": [(FRONT, "10.1.0.7")],
                              "ports": [("udp", 8080, 8080, "host")]}
    out = _net_check(services, tasks, net)
    assert out["host_port_conflicts"] == 1


def test_room_sums_live_reservations_over_the_fleet():
    nodes = _nodes(2)
    svcs = {"s": _spec(3, cpu=2, mem=3)}
    tasks = _tasks("s", ["n0", "n1", "manager"]) + [("x", "s", "n0", 64)]
    assert check.room(nodes, svcs, tasks) == {"cpu": [4, 20],
                                              "mem": [6, 20]}
