"""bench/trace.py: busy and idle time, kernel time, collective exposure and
the labelling of idle gaps, on hand-made events and on a recorded trace."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"
NS = 1e-9


def events():
    ev = trace.Events()
    ev.device_ops["/device:TPU:0"] = [
        ("fusion.1", 0, 100), ("ssd_scan.3", 100, 300),
        ("all-reduce.1", 250, 400), ("fusion.2", 500, 600),
        ("fusion.9", 1200, 1300),  # outside the window
    ]
    ev.host = [("bench.window", 0, 1000), ("dispatch", 400, 450),
               ("wait", 450, 1000)]
    return ev


def test_union_and_subtract():
    u = trace.union([(5, 9), (0, 3), (2, 4), (20, 30)], 1, 25)
    assert u == [[1, 4], [5, 9], [20, 25]]
    assert trace.length(u) == 12
    assert trace.subtract([[0, 10], [20, 30]], [[2, 3], [8, 22]]) == [
        [0, 2], [3, 8], [22, 30]]


def test_busy_idle_kernel_and_collectives():
    s = trace.summarize(events())
    assert s.window_s == pytest.approx(1000 * NS)
    assert s.busy_s == pytest.approx(500 * NS)  # [0, 400) and [500, 600)
    assert s.kernel_s("ssd_scan") == pytest.approx(200 * NS)
    assert s.collective_s == pytest.approx(150 * NS)
    assert s.exposed_collective_s == pytest.approx(100 * NS)  # [300, 400)
    assert s.top_ops(1) == [("ssd_scan.3", pytest.approx(200 * NS))]


def test_a_call_cut_by_the_window_is_not_counted_whole():
    ev = events()
    ev.device_ops["/device:TPU:0"] += [("ssd_scan.3", 900, 1100)]
    s = trace.summarize(ev)
    assert s.kernel_s("ssd_scan") == pytest.approx(300 * NS)
    assert s.kernel_calls("ssd_scan") == (1, pytest.approx(200 * NS))


def test_idle_gaps_are_labelled_by_the_host():
    s = trace.summarize(events())
    # [400, 500) has its middle in "wait"; so has [600, 1000)
    assert s.top_gaps() == [("wait", pytest.approx(500 * NS))]


def test_busy_is_averaged_over_devices():
    ev = events()
    ev.device_ops["/device:TPU:1"] = [("fusion.1", 0, 1000)]
    s = trace.summarize(ev)
    assert s.busy_s == pytest.approx((500 + 1000) / 2 * NS)


def test_a_trace_without_a_window_is_refused():
    ev = events()
    ev.host = [h for h in ev.host if h[0] != "bench.window"]
    with pytest.raises(ValueError):
        trace.summarize(ev)


@pytest.mark.skipif(not (DATA / "tiny.xplane.pb").exists(),
                    reason="no recorded trace")
def test_recorded_tpu_trace():
    """Three steps of ``jnp.tanh(x @ x) @ x`` on a (256, 256) bfloat16 x,
    each under "dispatch" and "wait", traced on one TPU v5 lite with the
    options ``bench.harness.Profile`` uses."""
    s = trace.summarize(trace.read(str(DATA / "tiny.xplane.pb")))
    assert 0 < s.busy_s < s.window_s
    assert s.top_ops()
    assert s.collective_s == 0
    assert {g for g, _ in s.top_gaps()} <= {"dispatch", "wait", "other"}
