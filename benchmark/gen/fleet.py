"""Fleet generator: READY worker nodes for one configuration.

Every node has the configuration's cpu and memory (scheduler_perf's
node-default: 4 cores, 32 GiB) and a zone label, assigned round-robin by
node index over the configuration's zones. The fleet is the same for every
seed.

`plan()` is plain numpy and imports nothing of the program; `build_nodes()`
turns a plan into the program's `Node` objects for seeding.
"""
from __future__ import annotations

import numpy as np

CPU_NANOS = 1_000_000_000          # nano-cpus per core
MIB = 1 << 20


def plan(cfg: dict) -> dict:
    """Per-node columns of the fleet: ids, zone labels, capacities."""
    n = int(cfg["nodes"])
    zones = cfg["zones"]
    return {
        "ids": [f"node-{i:06d}" for i in range(n)],
        "zone": [zones[i % len(zones)] for i in range(n)],
        "cpu": np.full(n, int(cfg["node_cpu_cores"]) * CPU_NANOS, np.int64),
        "mem": np.full(n, int(cfg["node_memory_mib"]) * MIB, np.int64),
    }


def build_nodes(fleet: dict) -> list:
    """The program's Node objects for a plan: READY, ACTIVE, linux/amd64."""
    from swarmkit_tpu.api.objects import Node
    from swarmkit_tpu.api.specs import (
        Annotations,
        NodeDescription,
        Platform,
        Resources,
    )
    from swarmkit_tpu.api.types import NodeAvailability, NodeStatusState

    out = []
    cpu, mem = fleet["cpu"].tolist(), fleet["mem"].tolist()
    for i, nid in enumerate(fleet["ids"]):
        n = Node(id=nid)
        n.status.state = NodeStatusState.READY
        n.status.addr = f"10.{i // 62500}.{(i // 250) % 250}.{i % 250}"
        n.spec.availability = NodeAvailability.ACTIVE
        n.spec.annotations = Annotations(
            name=nid, labels={"zone": fleet["zone"][i]})
        n.description = NodeDescription(
            hostname=nid, platform=Platform(os="linux", architecture="amd64"),
            resources=Resources(nano_cpus=cpu[i], memory_bytes=mem[i]))
        out.append(n)
    return out
