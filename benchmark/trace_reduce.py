"""Reduction from a profiler trace and the program's spans to numbers.

`extract()` reads the `.xplane.pb` that `jax.profiler` wrote and keeps the
device planes' events as plain tuples on the host's wall clock (the
"Task Environment" plane's `profile_start_time` plus each event's offset).
`reduce()` works on that plain form only, so a small recorded trace tests it
(benchmark/tests/).

  busy_s       union of the intervals in which an XLA op ran on a device,
               clipped to the window, averaged over the devices used
  window_s     length of the window
  ops          device seconds per op, named <program>/<op>
  programs     device seconds per program (the "XLA Modules" line)
  gaps         the longest idle intervals, each named by the host span of
               the program that covered most of it ("unattributed" if none)
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xspace(log_dir: str) -> str | None:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def extract(path: str) -> dict:
    """{device plane name: {line name: [(name, start_ns, dur_ns)]}} on the
    wall clock, for every /device: plane."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    origin = 0
    for plane in pd.planes:
        if plane.name == "Task Environment":
            origin = dict(plane.stats).get("profile_start_time", 0)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [
                    (e.name, int(origin + e.start_ns), int(e.duration_ns))
                    for e in line.events]
        out[plane.name] = lines
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(planes: dict, t0_ns: int, t1_ns: int, spans=(),
           top: int = 10) -> dict | None:
    """Numbers of one traced window; None when no device plane has an op.
    spans: (name, start_ns, end_ns) host spans of the program."""
    used = {name: lines for name, lines in planes.items()
            if lines.get(OPS_LINE)}
    if not used:
        return None
    busy = 0.0
    ops = defaultdict(float)
    programs = defaultdict(float)
    idle = []
    for lines in used.values():
        mods = sorted((s, s + d, _SUFFIX.sub("", n))
                      for n, s, d in lines.get(MODULES_LINE, ()))
        for n, s, d in lines.get(MODULES_LINE, ()):
            a, b = max(s, t0_ns), min(s + d, t1_ns)
            if b > a:
                programs[_SUFFIX.sub("", n)] += (b - a) / 1e9
        clipped = []
        for n, s, d in lines[OPS_LINE]:
            a, b = max(s, t0_ns), min(s + d, t1_ns)
            if b <= a:
                continue
            clipped.append((a, b))
            owner = _owner(mods, s)
            # TPU op events carry their whole HLO instruction: keep its name
            n = n.split(" = ", 1)[0].lstrip("%")
            ops[f"{owner}/{n}" if owner else n] += (b - a) / 1e9
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged) / 1e9
        edge = t0_ns
        for a, b in merged:
            if a > edge:
                idle.append((edge, a))
            edge = max(edge, b)
        if t1_ns > edge:
            idle.append((edge, t1_ns))
    idle.sort(key=lambda g: g[1] - g[0], reverse=True)
    gaps = [[_label(a, b, spans), (b - a) / 1e9] for a, b in idle[:top]]
    return {
        "busy_s": busy / len(used),
        "window_s": (t1_ns - t0_ns) / 1e9,
        "devices": len(used),
        "ops": sorted(([k, v] for k, v in ops.items()),
                      key=lambda kv: kv[1], reverse=True),
        "programs": dict(programs),
        "gaps": gaps,
    }


def _owner(mods, t: int) -> str | None:
    """Name of the program whose execution interval holds time t."""
    lo, hi = 0, len(mods)
    while lo < hi:
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t <= mods[lo - 1][1]:
        return mods[lo - 1][2]
    return None


def _label(a: int, b: int, spans) -> str:
    best, name = 0, "unattributed"
    for n, s, e in spans:
        cover = min(b, e) - max(a, s)
        if cover > best:
            best, name = cover, n
    return name
