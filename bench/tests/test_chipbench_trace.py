"""The trace reduction: self time of nested device ops, phases by named
scope, idle gaps by host activity, and a recorded chip trace (one d
solve of a 16 x 16 grid on one TPU v5e) reduced to its known numbers."""
import gzip
import json

import pytest

from bench import trace_reduce as tr
from bench.tests.tiny import REPO

DATA = REPO / "bench" / "tests" / "data"


def test_self_times_of_nested_ops():
    ops = [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (4.0, 8.0, "b"),
           (5.0, 6.0, "c"), (12.0, 13.0, "d")]
    own = tr.self_times(ops)
    assert own == {"while": 4.0, "a": 2.0, "b": 3.0, "c": 1.0, "d": 1.0}


def test_op_phases_from_program_text():
    text = "\n".join([
        '  %fusion.1 = f32[8] fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(run)/while/body/repro.contraction/while/body/gather"}',
        '  ROOT %copy.2 = f32[8] copy(%x), metadata={op_name="jit(run)/'
        'while/body/closed_call/repro.separation/jit(searchsorted)/gather"}',
        '  %while.3 = (s32[]) while(%t), metadata={op_name="jit(run)/while"}',
        '  %add.4 = f32[] add(%a, %b)'])
    assert tr.op_phases(text) == {"fusion.1": "contraction",
                                  "copy.2": "separation", "while.3": "loop"}
    assert tr.op_name("%fusion.1 = f32[8] fusion(%p)") == "fusion.1"


def test_idle_gaps_by_host_activity():
    host = [(0.0, 10.0, "bench.solve"), (0.0, 1.0, "bench.transfer"),
            (1.0, 1.5, "bench.dispatch"), (1.5, 10.0, "bench.readback")]
    ops = [(2.0, 9.0, "%fusion.1 = f32[] fusion()")]
    red = tr.reduce_events(ops, host, {"fusion.1": "message_passing"})
    assert red.window_s == 10.0 and red.busy_s == 7.0
    assert red.phase_s == {"message_passing": 7.0}
    assert red.idle_gaps == [("transfer", 2.0), ("readback", 1.0)]
    assert red.breakdown()["device_ops"] == [["message_passing/fusion.1",
                                              7.0]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "d_grid16.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "d_grid16.xplane.pb.gz").read_bytes()))
    phases = json.loads(gzip.decompress(
        (DATA / "d_grid16.phases.json.gz").read_bytes()))
    return tr.load_trace(path), phases


def test_recorded_chip_trace_reduces_to_known_numbers(recorded):
    (ops, host), phases = recorded
    assert len(ops) == 2137
    assert [n for _, _, n in host].count("bench.solve") == 1
    red = tr.reduce_events(ops, host, phases)
    assert red.busy_s == pytest.approx(0.003994094, rel=1e-6)
    assert red.window_s == pytest.approx(0.007758149, rel=1e-6)
    assert red.phase_s == pytest.approx(
        {"separation": 0.001008557, "message_passing": 0.002341569,
         "loop": 0.000643968}, rel=1e-6)
    assert sum(red.phase_s.values()) == pytest.approx(red.busy_s)
    # busy is the union of the op intervals, counted independently
    spans = sorted((s, e) for s, e, _ in ops)
    busy, end = 0.0, -1.0
    for s, e in spans:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert red.busy_s == pytest.approx(busy, rel=1e-9)
    assert red.top_ops[0][0] == "message_passing/fusion.523"
    assert [g[0] for g in red.idle_gaps[:2]] == ["transfer", "readback"]
    assert [g[1] for g in red.idle_gaps[:2]] == pytest.approx(
        [0.002070985, 0.001693056], rel=1e-6)
