"""Service mix: what each created service asks of the scheduler.

The configuration lists its service kinds (constraints and spread
preferences) and the reservation of every replica (scheduler_perf's
pod-default: 0.1 core, 500 MiB). A kind may also name the overlay networks
its tasks attach to (`networks`), its endpoint mode (`endpoint_mode`, `vip`
or `dnsrr`) and the ports it publishes (`ports`: target, published, mode,
protocol; published 0 asks the allocator for a dynamic port, and every
service of the kind publishes a fixed one as given). A kind without these
keys makes the same spec as one from before they existed. Sizes follow a
Zipf law given by the traffic file (s = 1.2 over 1..1000 replicas, mean
about 72).

One block of services holds the Zipf quantiles at (i + 0.5) / block as
sizes, laid out in a low-discrepancy order (position p takes the size whose
rank is that of frac(p * golden ratio)), so that any run of consecutive
services, such as those a window reaches, holds nearly the same mix of small
and large ones; position p has kind p mod the number of kinds. The stream
repeats the block from an offset drawn from the seed: every seed offers the
same (size, kind) pairs in another order.

Specs are plain dicts; `to_spec()` builds the program's ServiceSpec.
"""
from __future__ import annotations

import numpy as np

MILLICORE = 1_000_000              # nano-cpus in a thousandth of a core
MIB = 1 << 20
GOLDEN = (5 ** 0.5 - 1) / 2


def zipf_quantiles(n: int, s: float, lo: int, hi: int) -> np.ndarray:
    """Sizes at the quantiles (i + 0.5) / n of Zipf(s) on [lo, hi]."""
    k = np.arange(lo, hi + 1, dtype=np.float64)
    cdf = np.cumsum(k ** -s)
    cdf /= cdf[-1]
    u = (np.arange(n) + 0.5) / n
    return (lo + np.searchsorted(cdf, u)).astype(np.int64)


class ServiceStream:
    """Endless seeded stream of plain service specs."""

    def __init__(self, cfg: dict, sizes: dict, seed: int, block: int,
                 prefix: str):
        self._kind_list = cfg["service_kinds"]
        self._cpu = int(cfg["task_cpu_millicores"]) * MILLICORE
        self._mem = int(cfg["task_memory_mib"]) * MIB
        ranked = np.sort(zipf_quantiles(block, sizes["zipf_s"], sizes["min"],
                                        sizes["max"]))
        weyl = (np.arange(block) * GOLDEN) % 1.0
        self._sizes = ranked[np.argsort(np.argsort(weyl))].tolist()
        self._pos = int(np.random.default_rng(seed).integers(block))
        self._prefix = prefix
        self.issued = 0

    def next(self) -> dict:
        p = self._pos % len(self._sizes)
        self._pos += 1
        k = self._kind_list[p % len(self._kind_list)]
        spec = {
            "name": f"{self._prefix}{self.issued:06d}",
            "kind": k["name"],
            "replicas": int(self._sizes[p]),
            "cpu": self._cpu,
            "mem": self._mem,
            "constraints": [list(c) for c in k["constraints"]],
            "prefs": list(k["prefs"]),
        }
        if "networks" in k:
            spec["networks"] = list(k["networks"])
        if "endpoint_mode" in k:
            spec["endpoint_mode"] = k["endpoint_mode"]
        if "ports" in k:
            spec["ports"] = [{"target": int(q["target"]),
                              "published": int(q.get("published", 0)),
                              "mode": q.get("mode", "ingress"),
                              "protocol": q.get("protocol", "tcp")}
                             for q in k["ports"]]
        self.issued += 1
        return spec


def to_spec(plain: dict, network_ids: dict | None = None):
    """The program's ServiceSpec for a plain spec; network_ids maps the
    names of the configuration's networks to the ids the store gave them
    (the control API resolves an attachment's target by id)."""
    from swarmkit_tpu.api.specs import (
        Annotations,
        ContainerSpec,
        EndpointSpec,
        NetworkAttachmentConfig,
        Placement,
        PlacementPreference,
        PortConfig,
        ServiceSpec,
        TaskSpec,
    )

    task = TaskSpec(runtime=ContainerSpec(image="busybox", command=["true"]))
    res = task.resources.reservations
    res.nano_cpus = plain["cpu"]
    res.memory_bytes = plain["mem"]
    task.placement = Placement(
        constraints=[f"node.labels.{k} {op} {v}"
                     for k, op, v in plain["constraints"]],
        preferences=[PlacementPreference(spread_descriptor=f"node.labels.{p}")
                     for p in plain["prefs"]])
    if plain.get("networks"):
        task.networks = [NetworkAttachmentConfig(target=network_ids[n])
                         for n in plain["networks"]]
    spec = ServiceSpec(annotations=Annotations(name=plain["name"]),
                       task=task, replicas=plain["replicas"])
    if "endpoint_mode" in plain or "ports" in plain:
        spec.endpoint = EndpointSpec(
            mode=plain.get("endpoint_mode", "vip"),
            ports=[PortConfig(protocol=p["protocol"], target_port=p["target"],
                              published_port=p["published"],
                              publish_mode=p["mode"])
                   for p in plain.get("ports", [])])
    return spec
