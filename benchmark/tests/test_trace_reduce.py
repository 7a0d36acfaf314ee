"""The reduction from device trace and program spans to numbers."""
import pytest

import trace_reduce as tr

MS = 1_000_000


def _planes():
    ops = [("fusion", 10 * MS, 5 * MS),          # 10-15, prog A
           ("sort", 12 * MS, 6 * MS),            # 12-18 overlaps
           ("fusion.1", 40 * MS, 10 * MS),       # 40-50, prog B
           ("copy", 95 * MS, 10 * MS)]           # 95-105, clipped at 100
    mods = [("jit__a(7)", 10 * MS, 8 * MS),
            ("jit__b(8)", 40 * MS, 10 * MS),
            ("jit__b(9)", 95 * MS, 10 * MS)]
    return {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}


def test_busy_idle_ops_programs_and_gaps():
    spans = [("sched.tick", 18 * MS, 41 * MS),
             ("tick.encode", 20 * MS, 30 * MS),
             ("raft.flush", 50 * MS, 95 * MS)]
    r = tr.reduce(_planes(), 0, 100 * MS, spans)
    assert r["window_s"] == pytest.approx(0.1)
    # union: 10-18, 40-50, 95-100
    assert r["busy_s"] == pytest.approx(0.023)
    assert dict(r["ops"])["jit__a/sort"] == pytest.approx(0.006)
    assert dict(r["ops"])["jit__b/copy"] == pytest.approx(0.005)
    assert r["programs"]["jit__b"] == pytest.approx(0.015)
    gaps = r["gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.045, 0.022, 0.010])
    assert [g[0] for g in gaps] == ["raft.flush", "sched.tick",
                                    "unattributed"]


def test_no_device_plane_gives_nothing():
    assert tr.reduce({}, 0, 100 * MS) is None
    assert tr.reduce({"/device:TPU:0": {tr.OPS_LINE: []}}, 0, 100) is None


def test_extract_reads_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: extract() reads the xplane file
    and keeps device planes only; the CPU has none, so nothing to reduce."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xspace(str(tmp_path))
    assert path is not None
    planes = tr.extract(path)
    assert not any(p.startswith("/device:") for p in planes)
    assert tr.reduce(planes, 0, 10**19) is None


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite chip: two jitted programs, jit_f
    (a 512x512 matmul and tanh) and jit_g (a cumsum), three calls each with
    10 ms host sleeps between them."""
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_two_programs.xplane.pb")
    planes = tr.extract(path)
    assert "/device:TPU:0" in planes
    events = planes["/device:TPU:0"][tr.OPS_LINE]
    t0 = min(s for _, s, _ in events)
    t1 = max(s + d for _, s, d in events)
    r = tr.reduce(planes, t0, t1)
    assert r["devices"] == 1
    assert set(r["programs"]) == {"jit_f", "jit_g"}
    assert 0 < r["busy_s"] < r["window_s"]
    assert all(" = " not in name for name, _ in r["ops"])
    # the host sleeps between calls are the longest idle gaps
    assert r["gaps"][0][1] > 0.005
