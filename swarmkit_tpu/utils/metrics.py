"""Hot-path latency histograms (the reference's prometheus timers:
store tx / lock-hold — memory.go:99-112, raft propose — raft.go:204-209,
dispatcher scheduling delay — dispatcher.go:72-77).

A tiny fixed-bucket histogram with a process-global registry; the metrics
collector appends these to its Prometheus text exposition. Observation is
a few dict ops under a lock — cheap enough for every store transaction.
"""
from __future__ import annotations

import bisect
import threading
from ..analysis.lockgraph import make_lock

# prometheus-style default buckets, seconds
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class Histogram:
    def __init__(self, name: str, help_: str = "",
                 buckets: tuple = DEFAULT_BUCKETS, labels: str = ""):
        self.name = name
        self.help = help_
        self.labels = labels          # pre-rendered 'k="v",...' or ""
        self.buckets = tuple(buckets)
        self._counts = [0] * (len(self.buckets) + 1)  # +inf tail
        self._sum = 0.0
        self._n = 0
        self._lock = make_lock('utils.metrics.histogram')

    def observe(self, seconds: float):
        i = bisect.bisect_left(self.buckets, seconds)
        with self._lock:
            self._counts[i] += 1
            self._sum += seconds
            self._n += 1

    def snapshot(self) -> tuple[list[int], float, int]:
        with self._lock:
            return list(self._counts), self._sum, self._n

    def prometheus_text(self, type_line: bool = True) -> str:
        counts, total, n = self.snapshot()
        lines = []
        if type_line:
            # HELP precedes TYPE (promtool order); families render their
            # own header and pass type_line=False per child
            lines.append(f"# HELP {self.name} {_escape_help(self.help)}")
            lines.append(f"# TYPE {self.name} histogram")
        lbl = (self.labels + ",") if self.labels else ""
        cum = 0
        for b, c in zip(self.buckets, counts):
            cum += c
            lines.append(f'{self.name}_bucket{{{lbl}le="{b}"}} {cum}')
        lines.append(f'{self.name}_bucket{{{lbl}le="+Inf"}} {n}')
        suffix = f"{{{self.labels}}}" if self.labels else ""
        lines.append(f"{self.name}_sum{suffix} {total:.6f}")
        lines.append(f"{self.name}_count{suffix} {n}")
        return "\n".join(lines)


def _escape_label_value(v) -> str:
    # Prometheus text exposition: backslash, double-quote and newline must
    # be escaped inside label values or the scrape breaks mid-page.
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(v) -> str:
    # HELP text: backslash and newline only (quotes are legal there)
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def _render_labels(label_names: tuple, values: tuple) -> str:
    return ",".join(f'{k}="{_escape_label_value(v)}"'
                    for k, v in zip(label_names, values))


class Counter:
    """Unlabeled monotonic counter. `inc` serializes under an internal
    lock — `+=` on an int attribute is not atomic across threads, and
    the telemetry rollup (utils/telemetry.py) sums these across nodes,
    so a lost increment here is a wrong cluster number there. The same
    internal-lock contract covers CounterFamily/Histogram; component
    code must NOT add its own ad-hoc guard locks around these."""

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._n = 0
        self._lock = make_lock('utils.metrics.counter')

    def inc(self, n: int = 1):
        with self._lock:
            self._n += n

    @property
    def value(self) -> int:
        with self._lock:
            return self._n

    def prometheus_text(self) -> str:
        return (f"# HELP {self.name} {_escape_help(self.help)}\n"
                f"# TYPE {self.name} counter\n"
                f"{self.name} {self.value}")


class CounterDict(dict):
    """A dict of named counters with an atomic `inc` — the shape the
    dispatcher's `metrics` bag needs: plain-dict READ surface (tests and
    the bench read `metrics["flushes"]`), internally-locked writes for
    keys bumped from several threads. Single-writer keys may keep using
    plain item assignment; any key incremented from more than one thread
    must go through `inc`."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._lock = make_lock('utils.metrics.counter_dict')

    def inc(self, key, n=1):
        with self._lock:
            self[key] = self.get(key, 0) + n


class CounterFamily:
    """Labeled monotonic counters (the grpc_prometheus
    grpc_server_handled_total shape): one family, one series per label
    tuple, created on first increment."""

    def __init__(self, name: str, help_: str, label_names: tuple):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._series: dict[tuple, int] = {}
        self._lock = make_lock('utils.metrics.counter_family')

    def inc(self, values: tuple, n: int = 1):
        with self._lock:
            self._series[values] = self._series.get(values, 0) + n

    def value(self, values: tuple) -> int:
        with self._lock:
            return self._series.get(values, 0)

    def prometheus_text(self) -> str:
        with self._lock:
            items = sorted(self._series.items())
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} counter"]
        for values, n in items:
            lines.append(
                f"{self.name}{{{_render_labels(self.label_names, values)}}}"
                f" {n}")
        return "\n".join(lines)


class HistogramFamily:
    """Labeled histograms (grpc_server_handling_seconds shape)."""

    def __init__(self, name: str, help_: str, label_names: tuple,
                 buckets: tuple = DEFAULT_BUCKETS):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self.buckets = buckets
        self._series: dict[tuple, Histogram] = {}
        self._lock = make_lock('utils.metrics.histogram_family')

    def child(self, values: tuple) -> Histogram:
        with self._lock:
            h = self._series.get(values)
            if h is None:
                h = Histogram(self.name, self.help, self.buckets,
                              labels=_render_labels(self.label_names,
                                                    values))
                self._series[values] = h
            return h

    def observe(self, values: tuple, seconds: float):
        self.child(values).observe(seconds)

    def prometheus_text(self) -> str:
        with self._lock:
            items = sorted(self._series.items())
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} histogram"]
        for _values, h in items:
            lines.append(h.prometheus_text(type_line=False))
        return "\n".join(lines)


_registry: dict[str, Histogram] = {}
_families: dict[str, object] = {}
_registry_lock = make_lock('utils.metrics.registry_lock')


def histogram(name: str, help_: str = "",
              buckets: tuple = DEFAULT_BUCKETS) -> Histogram:
    with _registry_lock:
        h = _registry.get(name)
        if h is None:
            h = Histogram(name, help_, buckets)
            _registry[name] = h
        return h


def counter(name: str, help_: str = "") -> Counter:
    with _registry_lock:
        c = _families.get(name)
        if c is None:
            c = Counter(name, help_)
            _families[name] = c
        return c


def counter_family(name: str, help_: str = "",
                   label_names: tuple = ()) -> CounterFamily:
    with _registry_lock:
        f = _families.get(name)
        if f is None:
            f = CounterFamily(name, help_, label_names)
            _families[name] = f
        return f


def histogram_family(name: str, help_: str = "",
                     label_names: tuple = ()) -> HistogramFamily:
    with _registry_lock:
        f = _families.get(name)
        if f is None:
            f = HistogramFamily(name, help_, label_names)
            _families[name] = f
        return f


def all_histograms() -> list[Histogram]:
    with _registry_lock:
        return list(_registry.values())


def all_families() -> list:
    with _registry_lock:
        return list(_families.values())


# --------------------------------------------------------------------------
# Telemetry snapshot codec (ISSUE 15): a compact JSON-safe encoding of the
# registry that agents piggyback on heartbeats and the manager-side
# aggregator merges into cluster-level families.
#
# Shape (version 1):
#   {"v": 1,
#    "counters":   {name: {"labels": [...], "help": str,
#                          "series": [[<label values>, n], ...]}},
#    "histograms": {name: {"labels": [...], "help": str, "buckets": [...],
#                          "series": [[<label values>, counts, sum, n],
#                                     ...]}},
#    "gauges":     {name: number}}
#
# Counters ship CUMULATIVE values and histograms full bucket vectors, so
# "latest report per node" is all the rollup state a manager needs —
# merge is a plain per-series sum.  merge_snapshot is ASSOCIATIVE and
# COMMUTATIVE (integer sums per key; series keyed by label-value tuples;
# gauges summed), so shard-partial rollups compose in any order.
# Everything inside is JSON-safe (lists, never tuples) — the wire codec
# and swarmbench's JSON report carry snapshots verbatim.
# --------------------------------------------------------------------------


def empty_snapshot() -> dict:
    return {"v": 1, "counters": {}, "histograms": {}, "gauges": {}}


def registry_snapshot(gauges: dict | None = None, families=None,
                      histograms=None) -> dict:
    """Snapshot the process registry (or, for tests/partial rollups, the
    explicit `families`/`histograms` lists) into the codec shape above.
    `gauges` is the caller's small additive gauge set (task-state
    census, queue depths) merged in as-is."""
    fams = all_families() if families is None else list(families)
    hists = all_histograms() if histograms is None else list(histograms)
    snap = empty_snapshot()
    for f in fams:
        if isinstance(f, Counter):
            snap["counters"][f.name] = {
                "labels": [], "help": f.help,
                "series": [[[], f.value]]}
        elif isinstance(f, CounterFamily):
            with f._lock:
                items = sorted(f._series.items())
            snap["counters"][f.name] = {
                "labels": list(f.label_names), "help": f.help,
                "series": [[[str(v) for v in values], n]
                           for values, n in items]}
        elif isinstance(f, HistogramFamily):
            with f._lock:
                items = sorted(f._series.items())
            snap["histograms"][f.name] = {
                "labels": list(f.label_names), "help": f.help,
                "buckets": list(f.buckets),
                "series": [[[str(v) for v in values]]
                           + [list(s[0]), s[1], s[2]]
                           for values, h in items
                           for s in (h.snapshot(),)]}
    for h in hists:
        counts, total, n = h.snapshot()
        snap["histograms"][h.name] = {
            "labels": [], "help": h.help, "buckets": list(h.buckets),
            "series": [[[], counts, total, n]]}
    if gauges:
        snap["gauges"].update({str(k): v for k, v in gauges.items()})
    return snap


def snapshot_series_count(snap: dict) -> int:
    """Cheap structural size of a snapshot (the dispatcher's defensive
    bound on hostile payloads — no JSON encode on the beat path)."""
    try:
        return (sum(len(f.get("series", ())) for f in
                    snap.get("counters", {}).values())
                + sum(len(f.get("series", ())) for f in
                      snap.get("histograms", {}).values())
                + len(snap.get("gauges", {})))
    except AttributeError:
        return 0


def snapshot_within_budget(snap, max_cells: int = 200_000) -> bool:
    """Cheap structural budget over an UNTRUSTED snapshot: counts every
    container slot / scalar / string chunk visited and bails the moment
    the budget is crossed — len() is O(1), so one hostile 50M-element
    counts vector (or a giant string under an unknown key) is rejected
    without walking it and without a JSON encode on the beat path."""
    stack = [snap]
    cells = 0
    while stack:
        o = stack.pop()
        if isinstance(o, dict):
            cells += len(o)
            if cells > max_cells:
                return False
            stack.extend(o.keys())
            stack.extend(o.values())
        elif isinstance(o, (list, tuple)):
            cells += len(o)
            if cells > max_cells:
                return False
            stack.extend(o)
        elif isinstance(o, str):
            cells += 1 + len(o) // 64
        else:
            cells += 1
        if cells > max_cells:
            return False
    return True


def merge_snapshot(dst: dict, src: dict) -> dict:
    """Pure merge of two snapshots into a NEW snapshot: counter series
    sum per (name, label values), histogram bucket vectors sum
    element-wise (same bounds required — a bounds mismatch keeps the
    larger-n series and counts the drop under gauges["merge_dropped"]),
    gauges sum. Associative and commutative, so per-shard partial
    rollups compose in any order."""
    out = empty_snapshot()
    for snap in (dst, src):
        if not snap:
            continue
        for name, fam in snap.get("counters", {}).items():
            cur = out["counters"].setdefault(
                name, {"labels": list(fam.get("labels", ())),
                       "help": fam.get("help", ""), "series": []})
            have = {tuple(s[0]): s for s in cur["series"]}
            for values, n in fam.get("series", ()):
                key = tuple(values)
                if key in have:
                    have[key][1] += n
                else:
                    s = [list(values), n]
                    cur["series"].append(s)
                    have[key] = s
            cur["series"].sort(key=lambda s: s[0])
        for name, fam in snap.get("histograms", {}).items():
            cur = out["histograms"].setdefault(
                name, {"labels": list(fam.get("labels", ())),
                       "help": fam.get("help", ""),
                       "buckets": list(fam.get("buckets", ())),
                       "series": []})
            have = {tuple(s[0]): s for s in cur["series"]}
            compatible = list(fam.get("buckets", ())) == cur["buckets"]
            for values, counts, total, n in fam.get("series", ()):
                key = tuple(values)
                if key not in have:
                    if not compatible:
                        # a NEW series from a mismatched grid must not
                        # land raw under this family's bucket header —
                        # that would render its counts against wrong
                        # bounds. Same policy as the same-key case:
                        # drop and surface.
                        out["gauges"]["merge_dropped"] = \
                            out["gauges"].get("merge_dropped", 0) + 1
                        continue
                    s = [list(values), list(counts), total, n]
                    cur["series"].append(s)
                    have[key] = s
                elif compatible and len(counts) == len(have[key][1]):
                    s = have[key]
                    s[1] = [a + b for a, b in zip(s[1], counts)]
                    s[2] += total
                    s[3] += n
                else:
                    # incompatible bucket grid (mixed code versions):
                    # keep the series with more observations, surface
                    # the drop — never silently mix bucket spaces
                    if n > have[key][3]:
                        have[key][1] = list(counts)
                        have[key][2] = total
                        have[key][3] = n
                    out["gauges"]["merge_dropped"] = \
                        out["gauges"].get("merge_dropped", 0) + 1
            cur["series"].sort(key=lambda s: s[0])
        for name, v in snap.get("gauges", {}).items():
            out["gauges"][name] = out["gauges"].get(name, 0) + v
    return out


def snapshot_counter_value(snap: dict, name: str, values=()) -> int:
    """One counter series' value out of a snapshot (0 when absent) —
    the read helper rollup consumers and tests share."""
    fam = snap.get("counters", {}).get(name)
    if fam is None:
        return 0
    want = [str(v) for v in values]
    for series_values, n in fam.get("series", ()):
        if list(series_values) == want:
            return n
    return 0
