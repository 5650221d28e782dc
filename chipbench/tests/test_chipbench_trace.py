"""The trace reduction: busy union, idle share, top ops, gap labels, and
span self times."""
from types import SimpleNamespace

import pytest

from chipbench import trace_reduce as T

MS = 1e6  # nanoseconds


def _trace():
    """A window of 10 ms: ops busy 1-3, 2-4 (overlapping) and 6-7 ms; the
    host dispatches 0-5 ms (with a transfer at 4.5-5) and syncs 5-10 ms."""
    return T.Trace(
        devices={"/device:TPU:0": [("fusion.1", 1 * MS, 3 * MS),
                                   ("while.2", 2 * MS, 4 * MS),
                                   ("fusion.1", 6 * MS, 7 * MS)]},
        host=[[(T.WINDOW, 0, 10 * MS), ("chipbench.query", 0, 5 * MS),
               ("TransferToHost", 4.5 * MS, 5 * MS)],
              [("Sync", 5 * MS, 10 * MS)]],
    )


def test_busy_union_idle_and_top_ops():
    s = T.summarize(_trace())
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.004)  # 1-4 and 6-7 ms
    assert s.device_ops == [["fusion.1", pytest.approx(0.003)],
                            ["while.2", pytest.approx(0.002)]]


def test_gap_labels_take_the_innermost_host_event():
    s = T.summarize(_trace())
    gaps = dict(s.idle_gaps)
    # 0-1 ms under the query; 4-6 ms centres on 5 ms, where the transfer
    # (0.5 ms long) is innermost; 7-10 ms under the sync
    assert gaps == {"chipbench.query": pytest.approx(0.001),
                    "TransferToHost": pytest.approx(0.002),
                    "Sync": pytest.approx(0.003)}
    assert T.labels([[("a", 0, 1)]], [2.0]) == ["(no host event)"]


def test_window_annotation_required():
    t = _trace()
    t.host[0] = t.host[0][1:]
    with pytest.raises(ValueError, match="chipbench.window"):
        T.summarize(t)


def _with_plane(trace, name, ops):
    return T.Trace(devices=dict(trace.devices, **{name: ops}), host=trace.host)


def test_cell_planes_leave_out_another_chip():
    """A busy plane and an idle extra one: kept to the cell's one device,
    busy time, top ops and idle gaps read as on that plane alone."""
    one = _trace()
    two = _with_plane(one, "/device:TPU:1", [])
    cell = T.cell_planes(two, [SimpleNamespace(id=0)])
    assert list(cell.devices) == ["/device:TPU:0"] and cell.host is one.host
    assert T.summarize(cell) == T.summarize(one)
    assert T.summarize(two).busy_s == pytest.approx(T.summarize(one).busy_s / 2)
    assert list(T.cell_planes(two, [SimpleNamespace(id=1)]).devices) == ["/device:TPU:1"]
    with pytest.raises(ValueError, match="TPU:2"):
        T.cell_planes(two, [SimpleNamespace(id=0), SimpleNamespace(id=2)])


def test_every_number_a_mean_per_plane():
    """Two chips that ran alike read as one: top ops too, per chip."""
    one = _trace()
    two = _with_plane(one, "/device:TPU:1", one.devices["/device:TPU:0"])
    a, b = T.summarize(one), T.summarize(two)
    assert b.busy_s == pytest.approx(a.busy_s) and b.window_s == a.window_s
    assert b.device_ops == [[n, pytest.approx(t)] for n, t in a.device_ops]
    assert b.idle_gaps == [[n, pytest.approx(t)] for n, t in a.idle_gaps]


def _ev(name, ts, dur, tid=1):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": tid}


def test_span_self_time():
    spans = [_ev("quantum", 0, 100), _ev("pump", 10, 60),
             _ev("consume_async", 12, 20), _ev("poll", 40, 25),
             _ev("pause_migrate_resume", 45, 10), _ev("quantum", 200, 30),
             _ev("poll", 0, 7, tid=2)]
    assert T.self_us(spans, "quantum") == 40 + 30
    assert T.self_us(spans, "poll") == 15 + 7
    assert T.self_us(spans, "consume_async") == 20
    assert T.total_us(spans, "pause_migrate_resume") == 10
    assert T.spans_in(spans, 0, 100) == [s for s in spans if s["ts"] != 200]


def test_recorded_v5e_trace():
    """A trace recorded on one v5e: three queries, each a matmul fusion and
    a reduction, inside the benchmark's window annotation."""
    import os

    path = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")
    t = T.load(path)
    assert list(t.devices) == ["/device:TPU:0"]
    assert {n for n, _, _ in t.devices["/device:TPU:0"]} == {
        "jit__lambda/fusion", "jit__lambda/multiply_reduce_fusion"}
    s = T.summarize(t)
    assert s.window_s == pytest.approx(3.72949e-3)
    assert 0 < s.busy_s < s.window_s
    assert [n for n, _ in s.device_ops] == ["jit__lambda/fusion",
                                             "jit__lambda/multiply_reduce_fusion"]
    assert sum(x for _, x in s.idle_gaps) == pytest.approx(s.window_s - s.busy_s)
    assert s.idle_gaps[0][0] == "np.asarray(jax.Array)"
