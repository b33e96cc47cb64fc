"""The trace reduction, on a trace recorded on a TPU v5e (three calls of
the sweep kernel on 200,000 x 41 rows and one plain reduction), and on
made-up traces where the answer is known."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import trace as tr  # noqa: E402
from bench.harness.sweep import is_sweep  # noqa: E402

SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "trace_small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return tr.load(SMALL)


def test_recorded_trace_kernel_calls(small):
    assert list(small.devices) == ["/device:TPU:0"]
    ops = small.devices["/device:TPU:0"]
    calls = [op for op in ops if is_sweep(op.name)]
    assert len(calls) == 3
    assert {tr.operand_rows(op) for op in calls} == {(200704, 128)}
    assert all(600e3 < op.dur_ns < 800e3 for op in calls)
    assert not any(tr.is_collective(op) for op in ops)


def test_recorded_trace_busy_idle_breakdown(small):
    busy = tr.mean_busy_s(small)
    assert 0 < busy * 1e9 < small.window_ns
    ops = small.devices["/device:TPU:0"]
    assert busy * 1e9 <= sum(op.dur_ns for op in ops) + 1e-3
    idle = tr.idle_percent(small)
    assert 0 < idle < 100
    gaps = tr.idle_gaps(small)
    assert sum(e - s for s, e in gaps) == pytest.approx(
        small.window_ns * idle / 100, rel=1e-9)
    b = tr.breakdown(small)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fcm_accumulate_pallas.1"
    assert b["device_ops"][0][1] == pytest.approx(
        sum(op.dur_ns for op in ops if is_sweep(op.name)) / 1e9)
    lengths = [g[1] for g in b["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    assert all(isinstance(g[0], str) and g[0] for g in b["idle_gaps"])


def _op(name, start, dur):
    return tr.Op(name, float(start), float(dur))


def test_made_up_trace_union_window_collectives_and_gap_names():
    kern = ('%fcm_accumulate_pallas.3 = (f32[128,128]{1,0}, f32[1,128]{1,0},'
            ' f32[1,1]{1,0}) custom-call(f32[4096,128]{1,0} %p, '
            'f32[128,128]{1,0} %v), custom_call_target="tpu_custom_call"')
    ag = ('%all-gather-start.1 = (f32[23,41]{1,0}, f32[4,23,41]{2,1,0}) '
          'all-gather-start(f32[23,41]{1,0} %c), dimensions={0}')
    ar = '%all-reduce.2 = f32[] all-reduce(f32[] %q), to_apply=%add'
    loop = '%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b'
    dev0 = [_op(loop, 100, 500), _op(kern, 100, 200), _op(kern, 250, 150),
            _op(ag, 700, 50)]
    dev1 = [_op(ar, 800, 100)]
    host = [("bench.window", 0, 1000), ("compile", 400, 300),
            ("wait", 600, 20)]
    t = tr.Trace(0.0, 1000.0, {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                 host)
    assert tr.busy_intervals(dev0) == [(100, 600), (700, 750)]
    assert tr.mean_busy_s(t) == pytest.approx((550 + 100) / 2 / 1e9)
    assert tr.idle_percent(t) == pytest.approx(100 * (1 - 325 / 1000))
    assert [tr.is_collective(o) for o in dev0 + dev1] == [
        False, False, False, True, True]
    assert tr.opcode(kern) == "custom-call" and is_sweep(kern)
    assert tr.operand_rows(dev0[1]) == (4096, 128)
    assert not is_sweep(ag) and tr.opcode(ag) == "all-gather-start"
    b = tr.breakdown(t)
    assert "while.1" not in [k for k, _ in b["device_ops"]]
    assert b["device_ops"][0] == ["fcm_accumulate_pallas.3", 350 / 2 / 1e9]
    # device 1 idles 0..800: compile overlaps 300 of it, the window 800
    # (the whole gap) but is longer; the most overlap wins
    assert b["idle_gaps"][0] == ["bench.window", 800 / 1e9]
    assert tr.host_activity(t, 600, 700) == "compile"
    assert tr.host_activity(t, 605, 615) == "wait"
    # a window that starts late clips the busy time before it
    late = t._replace(start_ns=650.0, window_ns=350.0)
    assert tr.mean_busy_s(late) == pytest.approx((50 + 100) / 2 / 1e9)


def test_importing_the_reduction_loads_no_jax():
    code = ("import sys; sys.path[:0] = [%r]; import bench.harness.trace; "
            "print('jax' in sys.modules)" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
