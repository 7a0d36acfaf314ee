"""Operator observability listeners: --listen-metrics and --listen-debug.

The reference exposes Prometheus via --listen-metrics and pprof/expvar via
--listen-debug (swarmd/cmd/swarmd/main.go:5-9, 97-100, 266;
manager/manager.go:551-562 grpc_prometheus). The Python-native analogue:

  /metrics       Prometheus text (content type text/plain; version=0.0.4)
                 — object/node gauges + hot-path histograms
                 (manager/metrics.py MetricsCollector.prometheus_text)
                 + per-node component counters (WAL fsyncs, store op
                 counts, commit-worker depth/poison, heartbeat-wheel
                 entries/buckets) + the trace plane's derived stage
                 histograms when the tracer is armed
  /healthz       liveness probe
  /debug/stacks  all thread stacks (the pprof goroutine-dump analogue —
                 the same diagnostic the wedge detector emits)
  /debug/vars    expvar-style JSON snapshot (+ store op counts, failpoint
                 arm-state, trace arm-state — a leaked arm is visible
                 here without reading conftest output)
  /debug/profile?seconds=N
                 CPU profile of the live process (the pprof CPU-profile
                 analogue, VERDICT item 9): all threads sampled at
                 ~100 Hz for N seconds, reported as a pstats dump
                 sorted by cumulative time
  /debug/trace?seconds=N
                 collect spans for N seconds (arming the tracer for the
                 window if it was disarmed) and return JSON span trees
  /debug/trace/recent
                 the armed flight recorder's current contents as JSON
                 span trees (empty when disarmed)
  /debug/slo     task-lifecycle SLO snapshot from the armed lifecycle
                 recorder (utils/lifecycle.py): NEW→RUNNING percentiles
                 (exact + histogram-estimate), transition counts, and
                 the stage-attribution report; ?since= / ?window=N
                 restrict to the trailing recovery window
  /debug/tasks   ?id=<task>: that task's state-transition timeline;
                 without id, tracked tasks with their latest stage
  /debug/cluster cluster telemetry rollup (utils/telemetry.py +
                 manager/telemetry.py, leader only): merged node metric
                 snapshots, per-node freshness (stale nodes listed,
                 never averaged in), manager-local families;
                 ?window=N adds ring percentiles over the trailing
                 window; {"armed": false} when the plane is disarmed
                 or this node holds no aggregator

Bound to loopback by default; no TLS (match the reference's plaintext debug
listeners, which are operator-only surfaces).
"""
from __future__ import annotations

import json
import math
import sys
import threading
try:
    from ..analysis.lockgraph import make_lock
except ImportError:
    # file-mode load (tests/test_debug_profile.py execs this module
    # straight from its path so crypto-less environments skip the
    # package import chain) — the factory is still reachable absolutely
    from swarmkit_tpu.analysis.lockgraph import make_lock
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


def dump_stacks() -> str:
    lines = []
    frames = sys._current_frames()
    for t in threading.enumerate():
        frame = frames.get(t.ident)
        lines.append(f"--- thread {t.name} (daemon={t.daemon}) ---")
        if frame is not None:
            lines.extend(traceback.format_stack(frame))
    return "\n".join(lines)


def profile_dump(seconds: float, interval: float = 0.01) -> str:
    """CPU profile of every live thread, formatted as a pstats dump.

    Go's pprof CPU profile is a SAMPLING profiler; CPython's tracing
    profilers (cProfile) attach per-thread only, so enabling one inside
    an HTTP handler would profile nothing but the handler's own sleep.
    The closest live-daemon analogue: sample `sys._current_frames()`
    across all threads at ~1/interval Hz, synthesize cProfile-shaped
    stats ((file, line, func) -> (cc, nc, tt, ct, callers), tt/ct from
    leaf/cumulative sample counts x interval), and print them through
    `pstats.Stats` sorted by cumulative — the exact report an operator
    reads out of `cProfile` runs, from a live wedged daemon.

    Caveat the header states: frames accrue samples by WALL time, not
    CPU time — unlike SIGPROF-driven pprof, a thread parked in
    Condition.wait collects samples at the same rate as a busy one, so
    idle wait stacks rank alongside hot ones (which is also what makes
    this the right tool for WEDGED daemons: the stuck stack is exactly
    what surfaces)."""
    import io
    import pstats
    from collections import Counter

    leaf: Counter = Counter()
    cum: Counter = Counter()
    me = threading.get_ident()
    samples = 0
    deadline = time.monotonic() + seconds
    while True:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue              # not the sampler itself
            stack = []
            f = frame
            while f is not None:
                code = f.f_code
                stack.append((code.co_filename, code.co_firstlineno,
                              code.co_name))
                f = f.f_back
            if stack:
                leaf[stack[0]] += 1
                for key in set(stack):   # one cum tick per frame per sample
                    cum[key] += 1
        samples += 1
        if time.monotonic() >= deadline:
            break
        # sampling profiler: the wall-clock pacing IS the sample grid —
        # not a retry loop  # lint: allow(ad-hoc-sleep)
        time.sleep(interval)

    stats = {k: (c, c, leaf.get(k, 0) * interval, c * interval, {})
             for k, c in cum.items()}

    class _Synth:                      # duck-typed pstats source
        def create_stats(self):
            self.stats = stats

    out = io.StringIO()
    out.write(f"CPU profile: {samples} wall-clock samples over "
              f"{seconds:g}s at {interval * 1000:g}ms intervals, all "
              f"threads (tt/ct are sample-count x interval WALL-time "
              f"estimates; parked wait stacks accrue like busy ones)\n")
    ps = pstats.Stats(_Synth(), stream=out)
    ps.sort_stats("cumulative").print_stats(80)
    return out.getvalue()


def _find(node, attr):
    """Resolve a component off the node or its manager (the two shapes
    DebugServer is constructed around: SwarmNode and bare test stubs)."""
    v = getattr(node, attr, None)
    if v is not None:
        return v
    return getattr(getattr(node, "manager", None), attr, None)


def component_metrics_text(node) -> str:
    """Per-node component counters that were bench-only/internal until
    ISSUE 5: raft storage fsyncs, store op counts, the commit plane's
    queue depth + poison count, and heartbeat-wheel occupancy. Every
    lookup is defensive — a worker node (no raft), a stub, or a
    pre-leadership manager simply contributes fewer families."""
    # absolute import: this module is also loaded straight from its
    # file in crypto-less environments (see the lockgraph import above),
    # where relative imports have no package context
    from swarmkit_tpu.utils.metrics import _escape_label_value

    lines: list[str] = []

    def fam(name, help_, type_, samples):
        if not samples:
            return
        lines.append(f"# HELP {name} {help_}")
        lines.append(f"# TYPE {name} {type_}")
        lines.extend(samples)

    storage = getattr(_find(node, "raft"), "storage", None)
    if storage is not None and hasattr(storage, "wal_fsyncs"):
        fam("swarm_raft_wal_fsyncs_total",
            "WAL group-append fsyncs on this node (one per ready-flush "
            "batch; amortized per commit under load)", "counter",
            [f"swarm_raft_wal_fsyncs_total {storage.wal_fsyncs}"])
        fam("swarm_raft_meta_fsyncs_total",
            "hardstate/membership/snapshot/dir fsyncs on this node",
            "counter",
            [f"swarm_raft_meta_fsyncs_total {storage.meta_fsyncs}"])
    raft = _find(node, "raft")
    if raft is not None and hasattr(raft, "snap_chunks_sent"):
        # recovery plane (ISSUE 18): exposed generically off the live
        # snap_* counter surface so a new recovery counter appears here
        # WITHOUT a hand edit (the exposition drift guard walks it)
        ints, floats = [], []
        for key in sorted(a for a in vars(raft) if a.startswith("snap_")
                          and a != "snap_stream_max_bytes"):  # config knob
            v = getattr(raft, key)
            lbl = _escape_label_value(key)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if isinstance(v, int):
                ints.append(f'swarm_raft_recovery_total'
                            f'{{counter="{lbl}"}} {v}')
            else:
                floats.append(f'swarm_raft_recovery_seconds'
                              f'{{stat="{lbl}"}} {v}')
        fam("swarm_raft_recovery_total",
            "raft recovery plane counters (snapshot chunks sent/resent/"
            "rejected, suffix resumes, installs)", "counter", ints)
        fam("swarm_raft_recovery_seconds",
            "raft recovery plane timings (cumulative snapshot install "
            "seconds)", "counter", floats)
    op_counts = getattr(_find(node, "store"), "op_counts", None)
    if op_counts:
        fam("swarm_store_ops_total",
            "store operations by kind (view/update transactions, "
            "per-table finds)", "counter",
            [f'swarm_store_ops_total{{op="{_escape_label_value(op)}"}} {n}'
             for op, n in sorted(op_counts.items())])
    disp = _find(node, "dispatcher")
    disp_metrics = getattr(disp, "metrics", None)
    if disp_metrics:
        # the flush-plane counter bag, exposed generically so a new key
        # appears here WITHOUT a hand edit (the exposition drift guard
        # in tests/test_metrics_exposition.py walks the live dict)
        ints, floats = [], []
        for key in sorted(disp_metrics):
            v = disp_metrics[key]
            lbl = _escape_label_value(key)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if isinstance(v, int):
                ints.append(f'swarm_dispatcher_plane_total'
                            f'{{counter="{lbl}"}} {v}')
            else:
                floats.append(f'swarm_dispatcher_plane'
                              f'{{stat="{lbl}"}} {v}')
        fam("swarm_dispatcher_plane_total",
            "dispatcher fan-out plane counters (flushes, flush_tx, "
            "ships, wire_copies, dirty_walks, ...)", "counter", ints)
        fam("swarm_dispatcher_plane",
            "dispatcher fan-out plane stats (last_flush_s, ...)",
            "gauge", floats)
    broker = _find(node, "log_broker")
    broker_snap = getattr(broker, "metrics_snapshot", None)
    if broker_snap is not None:
        # log fan-out plane (ISSUE 20): the broker's always-on counter
        # surface, exposed generically off the live snapshot so a new
        # key appears here WITHOUT a hand edit (the exposition drift
        # guard walks the live dict the same way)
        ints, floats = [], []
        for key, v in sorted(broker_snap().items()):
            lbl = _escape_label_value(key)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if isinstance(v, int):
                ints.append(f'swarm_logbroker_plane_total'
                            f'{{counter="{lbl}"}} {v}')
            else:
                floats.append(f'swarm_logbroker_plane'
                              f'{{stat="{lbl}"}} {v}')
        fam("swarm_logbroker_plane_total",
            "log fan-out plane counters (published, delivered, shed, "
            "shed_windows, pump_jobs, listener_disconnects, ...)",
            "counter", ints)
        fam("swarm_logbroker_plane",
            "log fan-out plane stats", "gauge", floats)
    wheel = getattr(disp, "_hb_wheel", None)
    if wheel is not None:
        fam("swarm_heartbeat_wheel_entries",
            "sessions armed on the dispatcher heartbeat wheel", "gauge",
            [f"swarm_heartbeat_wheel_entries {len(wheel)}"])
        fam("swarm_heartbeat_wheel_buckets",
            "live buckets on the dispatcher heartbeat wheel", "gauge",
            [f"swarm_heartbeat_wheel_buckets {wheel.bucket_count}"])
        fam("swarm_heartbeat_wheel_ticks_total",
            "heartbeat-wheel ticker fires", "counter",
            [f"swarm_heartbeat_wheel_ticks_total {wheel.ticks}"])
        fam("swarm_heartbeat_wheel_expired_total",
            "heartbeat expirations delivered by the wheel", "counter",
            [f"swarm_heartbeat_wheel_expired_total {wheel.fired}"])
    worker = None
    mgr = getattr(node, "manager", None)
    for c in (getattr(mgr, "_leader_components", None) or ()):
        w = getattr(c, "_commit_worker", None)
        if w is not None:
            worker = w
            break
    if worker is None:
        worker = getattr(getattr(node, "scheduler", None),
                         "_commit_worker", None)
    if worker is not None:
        fam("swarm_commit_worker_queue_depth",
            "async commit plane: heavy commits submitted but not yet "
            "retired", "gauge",
            [f"swarm_commit_worker_queue_depth {worker.pending}"])
        fam("swarm_commit_worker_poisoned",
            "async commit plane: 1 while the worker holds an unraised "
            "exception (heals at the next barrier)", "gauge",
            [f"swarm_commit_worker_poisoned {int(worker.failed)}"])
        fam("swarm_commit_worker_jobs_total",
            "async commit plane: heavy commits retired", "counter",
            [f"swarm_commit_worker_jobs_total {worker.jobs_total}"])
        fam("swarm_commit_worker_poison_total",
            "async commit plane: poison episodes (worker-side commit "
            "crashes)", "counter",
            [f"swarm_commit_worker_poison_total {worker.poisoned_total}"])
    return "\n".join(lines)


class DebugServer:
    """One HTTP listener serving the observability surface for a node."""

    def __init__(self, addr: str, node):
        host, _, port = addr.rpartition(":")
        self.node = node
        # serializes /debug/trace?seconds=N captures (see _trace)
        self._trace_window_lock = make_lock('node.debugserver.trace_window_lock')
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _reply(self, body: str, ctype="text/plain; charset=utf-8",
                       code=200):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                try:
                    if self.path == "/metrics":
                        # the Prometheus text-format version the scraper
                        # content-negotiates on (satellite: exposition fix)
                        self._reply(outer._metrics_text(),
                                    ctype="text/plain; version=0.0.4; "
                                          "charset=utf-8")
                    elif self.path == "/healthz":
                        self._reply("ok\n")
                    elif self.path == "/debug/stacks":
                        self._reply(dump_stacks())
                    elif self.path == "/debug/vars":
                        self._reply(json.dumps(outer._vars(), indent=2),
                                    ctype="application/json")
                    elif self.path.startswith("/debug/trace"):
                        self._reply(json.dumps(outer._trace(self.path),
                                               indent=2),
                                    ctype="application/json")
                    elif self.path.startswith("/debug/slo"):
                        self._reply(json.dumps(outer._slo(self.path),
                                               indent=2),
                                    ctype="application/json")
                    elif self.path.startswith("/debug/cluster"):
                        self._reply(json.dumps(outer._cluster(self.path),
                                               indent=2),
                                    ctype="application/json")
                    elif self.path.startswith("/debug/tasks"):
                        self._reply(json.dumps(outer._tasks(self.path),
                                               indent=2),
                                    ctype="application/json")
                    elif self.path.startswith("/debug/profile"):
                        from urllib.parse import parse_qs, urlparse

                        q = parse_qs(urlparse(self.path).query)
                        try:
                            seconds = float(q.get("seconds", ["1"])[0])
                        except ValueError:
                            seconds = 1.0
                        # cap: the sampler blocks this handler thread
                        # (ThreadingHTTPServer — other endpoints stay
                        # responsive), not the daemon
                        self._reply(profile_dump(
                            max(0.05, min(seconds, 60.0))))
                    else:
                        self._reply("not found\n", code=404)
                except Exception as exc:  # surface, don't kill the listener
                    self._reply(f"error: {exc}\n", code=500)

        self._httpd = ThreadingHTTPServer((host or "127.0.0.1", int(port)),
                                          Handler)
        self.addr = "%s:%d" % self._httpd.server_address[:2]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="debug-http")

    def _metrics_text(self) -> str:
        node = self.node
        parts = []
        mgr = getattr(node, "manager", None)
        collector = None
        if mgr is not None:
            for c in getattr(mgr, "_leader_components", []):
                if hasattr(c, "prometheus_text"):
                    collector = c
                    break
        if collector is not None:
            parts.append(collector.prometheus_text())
        else:
            # non-leader / worker: hot-path histograms + per-RPC families
            # still exist (absolute import — file-mode load, see above)
            from swarmkit_tpu.utils.metrics import (
                all_families,
                all_histograms,
            )

            parts.extend(
                [h.prometheus_text() for h in all_histograms()]
                + [f.prometheus_text() for f in all_families()])
        comp = component_metrics_text(node)
        if comp:
            parts.append(comp)
        # cluster rollup families (ISSUE 15): the leader's aggregator
        # renders swarm_cluster_* next to the per-process families
        from swarmkit_tpu.utils import telemetry

        agg = telemetry.aggregator()
        if agg is not None and telemetry.enabled():
            try:
                parts.append(agg.prometheus_text())
            except Exception:
                pass  # a degraded rollup must not break the scrape
        return "\n".join(p for p in parts if p)

    def _cluster(self, path: str) -> dict:
        """/debug/cluster: the telemetry rollup (merged node snapshots,
        freshness, manager families); ?window=N adds nearest-rank
        percentiles over the ring's trailing window."""
        from urllib.parse import parse_qs, urlparse

        from swarmkit_tpu.utils import telemetry

        agg = telemetry.aggregator()
        if agg is None:
            return {"armed": telemetry.enabled(), "aggregator": False}
        q = parse_qs(urlparse(path).query)
        window = None
        try:
            if "window" in q:
                window = float(q["window"][0])
        except ValueError:
            window = None
        return agg.rollup(window_s=window)

    def _trace(self, path: str) -> dict:
        """/debug/trace?seconds=N and /debug/trace/recent: JSON span
        trees from the flight recorder. The windowed form arms the
        tracer for the window when it was disarmed — an operator gets a
        trace capture from a live daemon without restarting it."""
        from urllib.parse import parse_qs, urlparse

        from swarmkit_tpu.utils import trace

        parsed = urlparse(path)
        if parsed.path.rstrip("/").endswith("/recent"):
            r = trace.recorder()
            return {"armed": r is not None,
                    "spans": r.spans_recorded if r is not None else 0,
                    "traces": r.trees() if r is not None else []}
        q = parse_qs(parsed.query)
        try:
            seconds = float(q.get("seconds", ["1"])[0])
        except ValueError:
            seconds = 1.0
        seconds = max(0.05, min(seconds, 30.0))
        # windowed captures SERIALIZE (one lock across arm+sleep+disarm):
        # an overlapping request must not have its window truncated by
        # the first one's disarm, nor report "armed" for a recorder that
        # is about to be torn down. Blocks this handler thread only
        # (ThreadingHTTPServer); /debug/trace/recent stays lock-free.
        with self._trace_window_lock:
            r = trace.recorder()
            temporary = r is None
            if temporary:
                r = trace.arm()
            try:
                # operator-requested real-time capture window (not a
                # retry loop)  # lint: allow(ad-hoc-sleep)
                time.sleep(seconds)
                trees = r.trees(seconds=seconds + 0.05)
            finally:
                # never clobber an arm that raced in (an operator's
                # trace.arm replaces the recorder — then it is theirs)
                if temporary and trace.recorder() is r:
                    trace.disarm()
        return {"armed": not temporary, "window_s": seconds,
                "spans": r.spans_recorded, "traces": trees}

    def _slo(self, path: str) -> dict:
        """/debug/slo: startup percentiles (exact recorder samples AND
        the conservative /metrics-histogram estimates), transition
        counts, and the stage-attribution report. `?since=<wall-clock
        seconds>` restricts to tasks that reached RUNNING in the
        trailing window (`?window=N` is sugar for since=now-N)."""
        from urllib.parse import parse_qs, urlparse

        from swarmkit_tpu.utils import lifecycle, slo

        r = lifecycle.recorder()
        if r is None:
            return {"armed": False}
        q = parse_qs(urlparse(path).query)
        since = None
        try:
            if "since" in q:
                since = float(q["since"][0])
            elif "window" in q:
                since = time.time() - float(q["window"][0])
        except ValueError:
            since = None
        # the canonical report (shared with control.get_slo_report),
        # extended with the debug-only extras
        out = slo.report(r, since=since)
        out["batches"] = r.batches
        # what an alerting pipeline scraping /metrics would see; a rank
        # in the +Inf tail serializes as null — json.dumps would emit
        # the non-RFC token `Infinity` and break strict parsers exactly
        # on the degraded cluster an operator is inspecting
        est = slo.histogram_quantile(lifecycle.startup_histogram(), 99)
        out["startup"]["p99_s_histogram"] = (
            None if est is not None and not math.isfinite(est) else est)
        out["transitions"] = {f"{a}->{b}": n for (a, b), n
                              in sorted(r.transition_counts().items())}
        return out

    def _tasks(self, path: str) -> dict:
        """/debug/tasks?id=<task>: one task's timeline; without id, the
        tracked task ids with their latest stage (newest-inserted last,
        capped at 200)."""
        from urllib.parse import parse_qs, urlparse

        from swarmkit_tpu.utils import lifecycle

        r = lifecycle.recorder()
        if r is None:
            return {"armed": False}
        q = parse_qs(urlparse(path).query)
        task_id = q.get("id", [""])[0]
        if task_id:
            tl = r.timeline(task_id)
            return {"armed": True, "id": task_id,
                    "events": [{"stage": s, "t": t} for s, t in tl]}
        # key-list copy + 200 short per-timeline fetches — never a deep
        # copy of every timeline under the recorder lock (this endpoint
        # is polled on degraded clusters, exactly when the record sites
        # contending on that lock are busiest)
        out = {}
        for tid in r.task_ids()[-200:]:
            tl = r.timeline(tid)
            if tl:
                out[tid] = tl[-1][0]
        return {"armed": True, "tasks": len(r), "latest_stage": out}

    def _vars(self) -> dict:
        from swarmkit_tpu.utils import (
            failpoints,
            lifecycle,
            telemetry,
            trace,
        )

        node = self.node
        out = {
            "node_id": getattr(node, "node_id", None),
            "addr": getattr(node, "addr", None),
            "is_leader": bool(getattr(node, "is_leader", False)),
            "threads": len(threading.enumerate()),
            # fault/trace plane arm-state: a leaked arm (a test, an
            # operator session) is visible to operators HERE, not only
            # in conftest teardown assertions
            "failpoints_armed": failpoints.active(),
            "trace_armed": trace.active(),
            "lifecycle_armed": lifecycle.active(),
            "telemetry_armed": telemetry.active(),
        }
        store = _find(node, "store")
        if store is not None and getattr(store, "op_counts", None) \
                is not None:
            out["store_ops"] = dict(store.op_counts)
        col = getattr(store, "columnar", None)
        if col is not None:
            # columnar plane counters (ISSUE 11): scatter/materialize/
            # query volumes next to the op counts they complement
            out["store_columnar"] = {
                "tasks": len(col),
                "node_vocab": len(col.nodes),
                "service_vocab": len(col.services),
                **dict(col.stats),
            }
        raft = getattr(node, "raft", None)
        if raft is not None:
            out["raft"] = {
                "id": raft.id,
                "role": str(raft.role),
                "term": raft.term,
                "members": len(raft.members),
                "commit": raft.commit_index,
            }
        return out

    def start(self):
        self._thread.start()

    def stop(self):
        try:
            if self._thread.is_alive():
                # shutdown() handshakes with serve_forever — calling it
                # on a never-started server blocks forever on the
                # is-shut-down event that only serve_forever sets
                self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:
            pass
