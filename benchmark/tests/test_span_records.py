"""Span records of a traced window, the readers built on them, and the clock
check of program spans against the device trace."""
import json
import os
import types

import pytest

import span_records as sr

MS = 1_000_000
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ctx(records, assigned=2000):
    return types.SimpleNamespace(t0=0.0, t1=100.0, records=records,
                                 assigned_in_window=assigned)


def _rec(name, s_ms, e_ms, thread="t", cpu=None, **attrs):
    return (name, s_ms * MS, e_ms * MS, thread, cpu, attrs)


def test_program_records_read_the_retired_recorder():
    """Spans that end inside the window, as tuples; none without a
    retired recorder."""
    from swarmkit_tpu.utils import trace

    trace.clear_retired_tail()
    assert sr.program_records(0.0, 1e12) is None
    with trace.armed():
        with trace.span("loop.allocator", events=3):
            pass
        trace.rec("raft.apply", 0.001)
    try:
        recs = sr.program_records(0.0, 1e12)
        assert [r[0] for r in recs] == ["loop.allocator", "raft.apply"]
        name, s, e, thread, cpu, attrs = recs[0]
        assert s <= e and attrs == {"events": 3} and cpu >= 0
        assert recs[1][4] is None
        assert sr.program_records(0.0, 1.0) == []
        ctx = types.SimpleNamespace(t0=0.0, t1=1e12)
        assert sr.window_records(ctx) == recs
        assert ctx.records == recs          # kept for the next reader
    finally:
        trace.clear_retired_tail()


READERS = {
    "orch_s_per_ktask": 0.5,
    "alloc_s_per_ktask": 1.0,
    "sched_drain_s_per_ktask": 0.25,
    "raft_ready_s_per_ktask": 0.75,
    "raft_actions_per_prop": 2.5,
    "host_cpu_s_per_ktask": (0.5 + 0.25 + 0.125 + 0.0625) / 2,
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers_on_records(metric):
    import harness

    recs = [
        _rec("loop.replicated-orchestrator", 0, 1000, "orch", cpu=0.5),
        _rec("loop.allocator", 1000, 3000, "alloc", cpu=0.25),
        _rec("sched.drain", 0, 500, "scheduler", cpu=0.125),
        _rec("raft.ready", 0, 1500, "raft", cpu=0.0625),
        _rec("raft.wal_fsync", 10, 20, "raft"),       # no cpu: not a root
        _rec("tick.encode", 10, 20, "scheduler", cpu=9.0),  # not a root
        _rec("raft.propose", 0, 10, "alloc", actions=2),
        _rec("raft.propose", 20, 30, "alloc", actions=3),
        _rec("raft.propose", 40, 50, "alloc"),       # a parent's span
    ]
    read = harness.load_reader(metric)
    assert read(_ctx(recs)) == pytest.approx(READERS[metric])
    # silent where the program keeps no records, or assigned nothing
    assert read(_ctx(None)) is None
    assert read(_ctx([])) is None


def test_readers_silent_on_a_program_without_the_spans():
    """A program without these spans (the parent of the PR that added
    them) files other names and no cpu: every new reader stays silent."""
    import harness

    recs = [_rec("sched.tick", 0, 1000, "scheduler"),
            _rec("raft.propose", 0, 10, "alloc")]
    for metric in READERS:
        assert harness.load_reader(metric)(_ctx(recs)) is None, metric


def test_host_spans_self_time_by_thread():
    recs = [
        _rec("sched.tick", 0, 100, "s", cpu=0.05),
        _rec("tick.encode", 10, 30, "s", cpu=0.02),
        _rec("tick.dispatch", 40, 50, "s", cpu=0.01),
        _rec("tick.dirty_scan", 12, 14, "s"),          # inside encode
        _rec("raft.ready", 0, 60, "r", cpu=0.01),
        _rec("raft.wal_fsync", 5, 45, "r"),
        # same name on another thread: adds up, nests nowhere else
        _rec("tick.encode", 0, 10, "w", cpu=0.01),
        # overlaps ready without nesting in it: top-level
        _rec("raft.propose", 50, 80, "r"),
    ]
    got = {n: (w, c) for n, w, c in sr.host_spans(recs)}
    assert got["sched.tick"] == pytest.approx((0.070, 0.02))
    assert got["tick.encode"] == pytest.approx((0.028, 0.03))
    assert got["raft.wal_fsync"] == pytest.approx((0.040, 0.0))
    assert got["raft.ready"] == pytest.approx((0.020, 0.01))
    assert got["raft.propose"] == pytest.approx((0.030, 0.0))
    assert sr.host_spans(recs, top=2)[0][0] == "sched.tick"


def _clock_case(shift_ms=0.0):
    """Three ticks 1 s apart: each dispatch at d launches an execution
    1 ms later, and the sync at d + 150 ms waits for it."""
    recs, execs = [], []
    for i in range(3):
        d = 1000 * i + 100
        recs.append(_rec("tick.dispatch", d, d + 5))
        recs.append(_rec("tick.device_sync", d + 150, d + 160))
        xs = (d + 1 + shift_ms) * MS
        execs.append((int(xs), int(xs + 3 * MS)))
    return execs, recs


def test_clock_slack_on_a_causal_trace():
    execs, recs = _clock_case()
    got = sr.clock_slack(execs, recs)
    # tightest margin: each execution starts 1 ms after its dispatch
    assert got == {"slack_s": pytest.approx(-0.001), "checked": 3,
                   "unpaired": 0}
    # a dispatch with no sync recorded after it is not checked
    got = sr.clock_slack(execs, recs[:-1])
    assert (got["checked"], got["unpaired"]) == (2, 1)
    # an execution of a wave dispatched before the recorder was armed is
    # claimed by no dispatch
    got = sr.clock_slack([(50 * MS, 53 * MS)] + execs, recs)
    assert got == {"slack_s": pytest.approx(-0.001), "checked": 3,
                   "unpaired": 0}


@pytest.mark.parametrize("shift_ms,worst_s", [(-3.0, 0.002),
                                              (-100.0, 0.099),
                                              (200.0, 0.044)])
def test_clock_slack_finds_a_shifted_device_clock(shift_ms, worst_s):
    """Device events 3 or 100 ms early start before their dispatch; 200 ms
    late they end after the sync that waited for them."""
    execs, recs = _clock_case(shift_ms)
    got = sr.clock_slack(execs, recs)
    assert got["slack_s"] == pytest.approx(worst_s)
    assert got["checked"] == 3


def test_tick_executions_from_planes():
    planes = {"/device:TPU:0": {sr.MODULES_LINE: [
        ("jit__resident_tick_impl(12)", 10 * MS, 2 * MS),
        ("jit__sparse_counts(3)", 13 * MS, 1 * MS),
        ("jit__resident_tick_impl(12)", 99 * MS, 2 * MS)]}}
    assert sr.tick_executions(planes, 0, 100 * MS) == [(10 * MS, 12 * MS)]


def test_clock_check_on_a_trace_recorded_on_chip(tmp_path):
    """A traced window of fleet1k.rollout recorded on one TPU v5 lite chip
    (benchmark/trace_detail.py --keep): every resident tick execution lies
    between its tick.dispatch start and its tick.device_sync end on the
    device trace's clock, within 50 us."""
    import gzip

    import trace_reduce

    with open(os.path.join(DATA, "v5e_window_spans.json")) as f:
        spans = json.load(f)
    path = tmp_path / "window.xplane.pb"
    with gzip.open(os.path.join(DATA, "v5e_window.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    planes = trace_reduce.extract(str(path))
    execs = sr.tick_executions(planes, spans["t0_ns"], spans["t1_ns"])
    recs = [tuple(r) + ({},) for r in spans["records"]]
    got = sr.clock_slack(execs, recs)
    assert got["checked"] >= 3, got
    assert got["slack_s"] <= 50e-6, got


@pytest.mark.parametrize("recorder", [True, False])
def test_trace_detail_on_a_tiny_cell(tmp_path, recorder):
    """trace_detail's run on the CPU at 64 nodes: the traced throughput,
    and with the recorder armed the host breakdown and the kept records;
    the harness is left as it was."""
    import time

    import tiny
    import trace_detail

    harness = tiny.harness
    before = (harness.load_reader, harness.trace_reduce.reduce,
              harness.trace_reduce.find_xspace)
    harness.CHECK_WAIT_S = 8.0
    result, detail = trace_detail.detail_run(
        "fleet1k.rollout", 2**33 + 7, 4.0, recorder=recorder,
        keep=str(tmp_path), t_process=time.perf_counter(),
        require_tpu=False, definition=tiny.definition("fleet1k.rollout"))
    assert result["correct"], result["checks"]
    assert detail["assigned_per_s"] > 0
    assert ("host_spans" in detail) == recorder
    assert (tmp_path / "window_spans.json").exists() == recorder
    if recorder:
        names = {n for n, _, _ in detail["host_spans"]}
        assert "raft.ready" in names or "loop.allocator" in names
    assert (harness.load_reader, harness.trace_reduce.reduce,
            harness.trace_reduce.find_xspace) == before
