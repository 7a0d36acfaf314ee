"""Tiny cell definitions for the CPU rehearsal (64 nodes, seconds-long).

A cell is built from BENCHMARK.json and its configuration and traffic files
by name, as a run builds it, and then shrunk."""
import time

import harness


def definition(cell: str) -> dict:
    d = harness.cell_definition(cell)
    d["config"].update(nodes=64)
    d["traffic"].update(warmup_s=2, outstanding_tasks=60)
    d["traffic"]["sizes"]["max"] = 16
    return d


def network_definition(cell: str = "fleet1k.rollout",
                       host_port: bool = True) -> dict:
    """The tiny cell with a stack's networks and endpoints: a /20 ingress
    and two /20 overlays (room for every address the 64-node fleet can
    hold), a VIP kind publishing a dynamic ingress port, a kind on both
    overlays that, with host_port, publishes host-mode port 8080 in every
    one of its services, and a DNSRR kind held to one zone.

    With host_port the program is not correct: its allocator claims a
    host-mode port for one service across the whole cluster (PERF.md, Open
    questions), so the kind's later services never get an endpoint."""
    d = definition(cell)
    d["config"]["networks"] = [
        {"name": "ingress", "subnet": "10.255.0.0/20", "ingress": True},
        {"name": "stack_front", "subnet": "10.1.0.0/20", "ingress": False},
        {"name": "stack_back", "subnet": "10.2.0.0/20", "ingress": False},
    ]
    edge = {"name": "edge", "constraints": [], "prefs": [],
            "networks": ["stack_front", "stack_back"]}
    if host_port:
        edge["ports"] = [{"target": 8080, "published": 8080, "mode": "host",
                          "protocol": "tcp"}]
    d["config"]["service_kinds"] = [
        {"name": "web", "constraints": [], "prefs": ["zone"],
         "networks": ["stack_front"],
         "ports": [{"target": 80, "published": 0, "mode": "ingress",
                    "protocol": "tcp"}]},
        edge,
        {"name": "db", "constraints": [["zone", "==", "moon-1"]],
         "prefs": [], "networks": ["stack_back"], "endpoint_mode": "dnsrr"},
    ]
    return d


def run(cell: str, seed: int = 2**33 + 5, seconds: float = 5.0,
        trace: bool = False, definition_=None, **kw) -> dict:
    harness.CHECK_WAIT_S = 8.0       # tiny fleets converge in a second
    return harness.run_cell(cell, seed, seconds, trace,
                            t_process=time.perf_counter(), require_tpu=False,
                            definition=definition_ or definition(cell), **kw)
