"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name `BENCHMARK.json` gives it:

  benchmark/configs/<config>.json    the deployment: fleet and guarantees
  benchmark/traffic/<traffic>.json   the parameters of the traffic generator
  benchmark/metrics/<metric>.py      a reader: read(ctx) -> number or None

A run starts one single-manager SwarmNode (jax scheduler backend, pipelined,
async commit, its own node drained), seeds the fleet through store
transactions, warms up on the cell's own traffic, drives the control API for
the window, waits for what is due, reads the placement back from the leader's
raft-committed store and hands it to `check.py`.

A configuration may give its services networks and endpoints. Every key is
optional; a configuration without them runs as one from before they existed:

  networks                 [{"name", "subnet", "ingress": bool}]: an entry
                           with ingress true replaces the manager's default
                           ingress network (whose /24 holds 253 addresses)
                           before any node is seeded, so every node's
                           ingress attachment comes from its subnet; the
                           others are overlay networks created through
                           ControlAPI.create_network with that subnet
  service_kinds[].networks       names from `networks` the kind's tasks
                                 attach to
  service_kinds[].endpoint_mode  "vip" (the default) or "dnsrr"
  service_kinds[].ports    [{"target", "published", "mode", "protocol"}]:
                           published 0 asks for a dynamic port, mode is
                           "ingress" or "host"; every service of the
                           kind publishes a fixed port as given

Set-up then creates the networks after the manager starts and before the
fleet is seeded (the `networks` phase), waits until each carries its subnet,
and, after seeding, until every seeded node holds its ingress attachment.
After the window the networks, node attachments, service endpoints and task
attachments are read back too, and `check.check_network` adds its numbers.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import check  # noqa: E402
import trace_reduce  # noqa: E402
from gen import fleet as fleet_gen  # noqa: E402
from gen.collector import Collector  # noqa: E402
from gen.services import to_spec  # noqa: E402
from gen.traffic import Client  # noqa: E402

TRACE_CAPACITY = 1 << 21           # program spans kept in a traced window
BLOCK = 180                        # services per permuted block
CHECK_WAIT_S = 60.0                # how long past the close the check waits


class BenchError(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ definitions
def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell_definition(name: str) -> dict:
    """The cell's entry, configuration, traffic and metric lists."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {
        "cell": cell,
        "config": load_json(cfg_entry["file"]),
        "traffic": load_json(f"benchmark/traffic/{cell['traffic']}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def load_reader(metric: str):
    """The reader of a metric: metrics/<metric>.py, or for a name split by
    the cells' end-to-end metric (`device_idle_share.deploy`) the reader
    of the quantity it splits (metrics/device_idle_share.py)."""
    base = metric
    while not os.path.isfile(os.path.join(BENCH_DIR, "metrics",
                                          base + ".py")):
        if "." not in base:
            raise BenchError(f"no reader for metric {metric!r}")
        base = base.rsplit(".", 1)[0]
    path = os.path.join(BENCH_DIR, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + base, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------- compile count
class CompileCounter:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    _instance = None

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------------- set-up
def start_manager(state_dir: str, cfg: dict):
    from swarmkit_tpu.agent.testutils import FakeExecutor
    from swarmkit_tpu.api.types import NodeAvailability
    from swarmkit_tpu.controlapi.errors import FailedPrecondition
    from swarmkit_tpu.node.daemon import SwarmNode

    node = SwarmNode(
        state_dir=state_dir,
        executor=FakeExecutor({"*": {"run_forever": True}},
                              hostname="bench-manager"),
        listen_addr="127.0.0.1:0", control_socket=False,
        scheduler_backend=cfg["scheduler_backend"],
        scheduler_pipeline=True, scheduler_async_commit=True)
    node.start()
    m = node.manager
    if m is None or not m.is_leader:
        node.stop()
        raise BenchError("the single manager did not become leader")
    # leadership flips before the leader's components have started; the
    # health status turns SERVING once the last of them has (the manager
    # offers no event to wait on, so this one set-up step polls)
    from swarmkit_tpu.manager.health import SERVING

    deadline = time.monotonic() + 60.0
    while m.health.check("leader") != SERVING:
        if time.monotonic() > deadline:
            node.stop()
            raise BenchError("the leader's components did not start")
        time.sleep(0.01)
    # the manager's own node takes no work; its agent's registration can
    # bump the version between read and write: re-read and retry
    import dataclasses

    for _ in range(100):
        own = m.control_api.get_node(node.node_id)
        try:
            m.control_api.update_node(
                own.id, own.meta.version,
                dataclasses.replace(own.spec,
                                    availability=NodeAvailability.DRAIN))
            return node
        except FailedPrecondition:
            continue
    node.stop()
    raise BenchError("could not drain the manager's own node")


def declares_network(cfg: dict) -> bool:
    """Whether the configuration gives its services networks, an endpoint
    mode or ports: then the check's network numbers are computed."""
    return bool(cfg.get("networks")) or any(
        key in k for k in cfg["service_kinds"]
        for key in ("networks", "endpoint_mode", "ports"))


def wait_store(store, done, what: str, timeout: float = 60.0) -> None:
    """Poll a read of the store until done(tx) holds (set-up only)."""
    deadline = time.monotonic() + timeout
    while not store.view(done):
        if time.monotonic() > deadline:
            raise BenchError(f"{what} within {timeout} s")
        time.sleep(0.01)


def create_networks(control, store, declared: list) -> dict:
    """Create the configuration's networks, the declared ingress in place of
    the default one; returns name -> id once each carries its subnet."""
    from swarmkit_tpu.api.specs import Annotations, NetworkSpec

    ordered = sorted(declared, key=lambda n: not n.get("ingress"))
    if ordered and ordered[0].get("ingress"):
        for n in control.list_networks():
            if n.spec.ingress:
                control.remove_network(n.id)
    ids = {}
    for n in ordered:
        ids[n["name"]] = control.create_network(NetworkSpec(
            annotations=Annotations(name=n["name"]),
            ingress=bool(n.get("ingress")),
            ipam={"subnet": n["subnet"]})).id

    def allocated(tx):
        got = [tx.get_network(i) for i in ids.values()]
        return all(g is not None and (g.driver_state or {}).get("subnet")
                   for g in got)

    wait_store(store, allocated, "the allocator gave no subnet to every "
               "declared network")
    return ids


def wait_ingress_attachments(store, node_ids: list, ingress_id: str) -> None:
    """Every seeded node holds its attachment on the ingress network."""
    def attached(tx):
        for nid in node_ids:
            n = tx.get_node(nid)
            if n is None or not any(
                    isinstance(a, dict) and a.get("network_id") == ingress_id
                    for a in n.attachments or ()):
                return False
        return True

    wait_store(store, attached, "the seeded nodes got no ingress attachment",
               timeout=120.0)


def seed_store(store, nodes: list) -> None:
    def make(n):
        def run(tx):
            tx.create(n)
        return run

    store.batch(lambda b: [b.update(make(n)) for n in nodes],
                pipeline_depth=16)


# ------------------------------------------------------------------ the run
def scheduler_of(node):
    from swarmkit_tpu.scheduler.scheduler import Scheduler

    for c in node.manager._leader_components:
        if isinstance(c, Scheduler):
            return c
    raise BenchError("the manager runs no Scheduler")


def counters(node, sched) -> dict:
    raft = node.raft
    return {"commits_applied": raft.commits_applied,
            "wal_fsyncs": raft.storage.wal_fsyncs,
            "ticks": sched.ticks,
            "tick_failures": sched.tick_failures,
            "full_uploads": getattr(sched._resident, "uploads_full", 0),
            "compiles": CompileCounter.get().compiles,
            "cache_hits": CompileCounter.get().cache_hits}


def plain_nodes(store, fleet: dict) -> dict:
    """The seeded workers as read back: labels, state, capacity."""
    from swarmkit_tpu.api.types import NodeAvailability, NodeStatusState

    got = {n.id: n for n in store.view(lambda tx: tx.find_nodes())}
    out = {}
    for i, nid in enumerate(fleet["ids"]):
        n = got.get(nid)
        out[nid] = {
            "labels": {"zone": fleet["zone"][i]},
            "ready": n is not None
            and n.status.state == NodeStatusState.READY,
            "active": n is not None
            and n.spec.availability == NodeAvailability.ACTIVE,
            "cpu": int(fleet["cpu"][i]), "mem": int(fleet["mem"][i]),
        }
    return out


def plain_network_state(store, task_objs: list, cfg: dict) -> dict:
    """Networks, node attachments, service endpoints and task attachments
    as plain values (check.check_network's `net`)."""
    def atts(objs) -> list:
        return [(a["network_id"], addr) for a in objs or ()
                if isinstance(a, dict) for addr in a.get("addresses", ())]

    def ports(ep) -> list:
        return [tuple(p) for p in (ep or {}).get("ports") or ()]

    networks, nodes, services = store.view(lambda tx: (
        tx.find_networks(), tx.find_nodes(), tx.find_services()))
    return {
        "networks": {n.id: {"name": n.spec.annotations.name,
                            "subnet": (n.driver_state or {}).get("subnet"),
                            "ingress": bool(n.spec.ingress)}
                     for n in networks},
        "declared": {n["name"]: n["subnet"]
                     for n in cfg.get("networks", ())},
        "nodes": {n.id: atts(n.attachments) for n in nodes},
        "services": {s.id: {"virtual_ips": [
                         tuple(v) for v in (s.endpoint or {}).get(
                             "virtual_ips") or ()],
                         "ports": ports(s.endpoint)} for s in services},
        "tasks": {t.id: {"attachments": atts(t.networks),
                         "ports": [(p.protocol, p.target_port,
                                    p.published_port, p.publish_mode)
                                   for p in (t.endpoint.ports
                                             if t.endpoint else ())]}
                  for t in task_objs},
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             control=None, definition: dict | None = None) -> dict:
    """One run; returns the result object (the last line's content).

    control: None, or a callable (nodes, services, tasks, due, seed) ->
    tasks that places the due services in the program's place; the check's
    numbers for its placement and its verdict go under the result's
    "control" and "control_correct" keys (benchmark/control.py).
    definition: the cell as `cell_definition` returns it (tests pass tiny
    ones)."""
    import jax

    d = definition or cell_definition(name)
    cell, cfg, traffic = d["cell"], d["config"], d["traffic"]
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise BenchError(f"no TPU: jax.devices()[0].platform is "
                         f"{d0.platform!r}")
    if len(devs) < int(cell["chips"]):
        raise BenchError(f"{name} needs {cell['chips']} chips, found "
                         f"{len(devs)}")
    CompileCounter.get()
    phases = {"import": time.perf_counter() - t_process}

    state_dir = tempfile.mkdtemp(prefix="bench-state-")
    node = None
    col = client = None
    try:
        t = time.perf_counter()
        node = start_manager(state_dir, cfg)
        m = node.manager
        store = m.store
        phases["manager"] = time.perf_counter() - t

        network_ids = {}
        if cfg.get("networks"):
            t = time.perf_counter()
            network_ids = create_networks(m.control_api, store,
                                          cfg["networks"])
            phases["networks"] = time.perf_counter() - t

        t = time.perf_counter()
        fleet = fleet_gen.plan(cfg)
        nodes = fleet_gen.build_nodes(fleet)
        phases["fleet"] = time.perf_counter() - t

        t = time.perf_counter()
        seed_store(store, nodes)
        ingress = [n["name"] for n in cfg.get("networks", ())
                   if n.get("ingress")]
        if ingress:
            wait_ingress_attachments(store, fleet["ids"],
                                     network_ids[ingress[0]])
        phases["seed"] = time.perf_counter() - t

        # warm-up: the cell's own traffic, from the same client the window
        # goes on with, so the programs of its shapes are loaded before t0
        t = time.perf_counter()
        col = Collector(store)
        sched = scheduler_of(node)
        client = Client(m.control_api, col, cfg, traffic, seed, "svc-",
                        BLOCK, network_ids)
        client.start()
        time.sleep(float(traffic["warmup_s"]))
        if client.error is not None:
            raise BenchError(f"create failed: {client.error!r}")

        rec = None
        log_dir = None
        if trace:
            from swarmkit_tpu.utils import trace as ptrace

            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            rec = ptrace.arm(capacity=TRACE_CAPACITY)
        before = counters(node, sched)
        t0 = time.time()
        phases["warmup"] = time.perf_counter() - t
        setup_s = time.perf_counter() - t_process
        n_before = len(client.created)
        time.sleep(max(0.0, t0 + seconds - time.time()))
        t1 = time.time()
        after = counters(node, sched)
        client.stop()
        if client.error is not None:
            raise BenchError(f"create failed: {client.error!r}")
        if trace:
            ptrace.disarm()
            jax.profiler.stop_trace()

        due = client.created
        due_replicas = sum(c["spec"]["replicas"] for c in due)
        due_ids = {c["id"] for c in due}

        def converged() -> bool:
            return all(col.assigned_by_service[c["id"]]
                       >= c["spec"]["replicas"] for c in due)

        # late answers are late, not wrong: the check waits up to
        # CHECK_WAIT_S past the close for every due replica
        col.wait_for(converged,
                     timeout=max(0.0, t1 + CHECK_WAIT_S - time.time()))
        mem = d0.memory_stats() or {}
        peak = mem.get("peak_bytes_in_use")

        spans = []
        device = None
        if trace:
            spans = [(r["name"], int(r["t0"] * 1e9),
                      int((r["t0"] + r["dur"]) * 1e9))
                     for r in rec.snapshot()
                     if r["dur"] > 0 and r["t0"] + r["dur"] >= t0
                     and r["t0"] <= t1]
            path = trace_reduce.find_xspace(log_dir)
            if path is not None:
                device = trace_reduce.reduce(
                    trace_reduce.extract(path), int(t0 * 1e9),
                    int(t1 * 1e9), spans)
            shutil.rmtree(log_dir, ignore_errors=True)

        # ---- read back and check
        services = {c["id"]: c["spec"] for c in client.created}
        with col.cond:
            counted = {tid: col.node[tid] for tid in col.assigned
                       if col.service_of.get(tid) in due_ids}
        task_objs = store.view(lambda tx: tx.find_tasks())
        tasks = [(t.id, t.service_id, t.node_id, int(t.status.state))
                 for t in task_objs]
        nodes_plain = plain_nodes(store, fleet)
        net = None
        if declares_network(cfg):
            net = plain_network_state(store, task_objs, cfg)

        def judge(placed: list) -> dict:
            out = check.check(nodes_plain, services, placed, counted,
                              due_ids)
            if net is not None:
                out.update(check.check_network(services, placed, due_ids,
                                               net))
            return out

        numbers = judge(tasks)
        control_numbers = None
        if control is not None:
            control_numbers = judge(
                control(nodes_plain, services, tasks, due_ids, seed))
        # the program's own failure counter is held to 0 beside the
        # reference check
        limits = dict(check.LIMITS, tick_failures=0)
        if net is not None:
            limits.update(check.NET_LIMITS)
        final = counters(node, sched)
        numbers["tick_failures"] = final["tick_failures"]
        room = check.room(nodes_plain, services, tasks)

        ctx = types.SimpleNamespace(
            cell=cell, config=cfg, traffic=traffic, seconds=float(seconds),
            t0=t0, t1=t1, setup_s=setup_s, collector=col,
            created=client.created, before=before, after=after, spans=spans,
            device=device)
        ctx.assigned_in_window = sum(
            1 for tid, ts in col.assigned.items()
            if t0 <= ts < t1 and col.service_of.get(tid) in due_ids)
        cutoff = t1 + CHECK_WAIT_S
        failed = sum(
            c["spec"]["replicas"] - sum(
                1 for tid in col.by_service[c["id"]]
                if col.assigned.get(tid, cutoff + 1) <= cutoff)
            for c in due)
        ticks = after["ticks"] - before["ticks"]
        say("setup: " + " ".join(f"{k}_s={v}" for k, v in phases.items())
            + f" total_s={setup_s}")
        say(f"window: seconds={t1 - t0} ticks={ticks}"
            f" assigned={ctx.assigned_in_window} services_created="
            f"{len(due) - n_before} compiles="
            f"{after['compiles'] - before['compiles']}"
            f" cache_hits={after['cache_hits'] - before['cache_hits']}"
            f" raft_commits="
            f"{after['commits_applied'] - before['commits_applied']}"
            f" wal_fsyncs={after['wal_fsyncs'] - before['wal_fsyncs']}"
            f" full_uploads={after['full_uploads'] - before['full_uploads']}")
        say(f"due: services={len(due)} replicas={due_replicas} "
            f"not_assigned={failed} wait_s={CHECK_WAIT_S}")
        say(f"room: reserved_cpu={room['cpu'][0]}/{room['cpu'][1]}"
            f" reserved_mem={room['mem'][0]}/{room['mem'][1]}")

        metrics = {}
        for mdef in (d["per_layer"] if trace else d["end_to_end"]):
            value = load_reader(mdef["name"])(ctx)
            if value is not None:
                metrics[mdef["name"]] = {"value": value, "unit": mdef["unit"]}
        result = {
            "correct": all(numbers[k] <= lim for k, lim in limits.items()),
            "attempted": due_replicas,
            "failed": failed,
            "metrics": metrics,
            "device": {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devs), "memory_peak_bytes": peak},
        }
        if trace:
            result["device"]["window_s"] = t1 - t0
            result["device"]["busy_s"] = device["busy_s"] if device else 0.0
            if device:
                result["breakdown"] = {"device_ops": device["ops"][:10],
                                       "idle_gaps": device["gaps"][:10]}
        if control_numbers is not None:
            result["control"] = control_numbers
            result["control_correct"] = check.verdict(control_numbers)
        result["checks"] = {k: {"value": v, "limit": limits[k]}
                            for k, v in numbers.items()}
        return result
    finally:
        if client is not None:
            client.stop()
        if col is not None:
            col.close()
        if node is not None:
            node.stop()
        shutil.rmtree(state_dir, ignore_errors=True)
