"""The parts of the phases: the innermost ``repro.<phase>.<part>`` scope
wins, the parts of a phase partition its time, the program's op names
are read from the trace itself, and the part metrics' readers, on a
recorded chip trace with part scopes (two pd rounds of a 16 x 16 grid on
one TPU v5e) and on one without (the d fixture of the trace tests)."""
import gzip
import json
import shutil

import pytest

from bench import harness, parts
from bench import trace_reduce as tr
from bench.tests.tiny import REPO

DATA = REPO / "bench" / "tests" / "data"

NESTED = {
    "fusion.1": "jit(run)/while/body/repro.contraction/"
                "repro.contraction.forest/while/body/scatter",
    "fusion.2": "jit(run)/while/body/repro.separation/"
                "repro.separation.triangles/repro.separation.long_bucket/"
                "while/body/gather",
    "sort.3": "jit(run)/while/body/repro.separation/"
              "repro.separation.cycles45/repro.separation.select/top_k",
    "copy.4": "jit(run)/while/body/repro.contraction/jit(lexsort)/sort",
    "add.5": "jit(run)/repro.message_passing/add",
    "while.6": "jit(run)/while"}
PART_METRICS = ["contraction.matching", "contraction.forest",
                "contraction.components", "contraction.merge",
                "contraction.csr", "separation.select",
                "separation.triangles", "separation.long_bucket",
                "separation.cycles45", "separation.chords"]


def test_op_parts_innermost_part_wins():
    assert parts.op_parts(NESTED) == {
        "fusion.1": "contraction.forest",
        "fusion.2": "separation.long_bucket",
        "sort.3": "separation.select",
        "copy.4": "contraction.other",
        "add.5": "message_passing.other",
        "while.6": "loop"}
    # the part scopes carry their phase's name: the phases, read from the
    # same names in the program's text, are as before
    text = "\n".join(f'  %{k} = f32[8] op(%p), metadata={{op_name="{v}"}}'
                     for k, v in NESTED.items())
    assert tr.op_phases(text) == {
        k: v.split(".")[0] for k, v in parts.op_parts(NESTED).items()}


def test_parts_partition_each_phase():
    host = [(0.0, 16.0, "bench.solve")]
    ops = [(0.0, 8.0, "%while.6 = (s32[]) while()"),
           (0.5, 2.5, "%fusion.1 = f32[8] fusion()"),
           (3.0, 4.0, "%copy.4 = f32[8] copy()"),
           (4.0, 7.5, "%fusion.2 = f32[8] fusion()"),
           (5.0, 5.5, "%sort.3 = s32[8] sort()"),
           (9.0, 9.25, "%add.5 = f32[] add()")]
    part_s = parts.part_seconds(ops, parts.op_parts(NESTED))
    assert part_s == {"contraction.forest": 2.0,
                      "contraction.other": 1.0,
                      "separation.long_bucket": 3.0,
                      "separation.select": 0.5,
                      "message_passing.other": 0.25}
    red = tr.reduce_events(ops, host, {
        k: v.split(".")[0] for k, v in parts.op_parts(NESTED).items()})
    for phase in ("contraction", "separation", "message_passing"):
        assert sum(t for p, t in part_s.items()
                   if p.split(".")[0] == phase) == red.phase_s[phase]


def _varint(x: int) -> bytes:
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _len(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_protobuf_fields():
    msg = (_varint(1 << 3) + _varint(300) + _len(2, b"abc")
           + _varint(3 << 3 | 1) + (7).to_bytes(8, "little")
           + _varint(4 << 3 | 5) + (9).to_bytes(4, "little")
           + _len(2, b"last"))
    got = [(n, bytes(v) if isinstance(v, memoryview) else v)
           for n, v in parts._fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"abc"), (3, 7), (4, 9), (2, b"last")]
    assert bytes(parts._field(memoryview(msg), 2)) == b"last"
    assert parts._field(memoryview(msg), 5) is None


def test_op_names_of_a_synthetic_trace():
    """XSpace > XPlane /host:metadata > event metadata > ``Hlo Proto``
    stat > HloProto > module > computation > instruction > op_name."""
    instr = _len(1, b"fusion.1") + _len(7, _len(2, NESTED["fusion.1"]
                                               .encode()))
    bare = _len(1, b"param.2")          # no metadata: left out
    hlo = _len(1, _len(3, _len(2, instr) + _len(2, bare)))
    stat = _varint(1 << 3) + _varint(7) + _len(6, hlo)
    event_md = _len(2, _varint(1 << 3) + _varint(3) + _len(5, stat))
    stat_md = _len(2, _varint(1 << 3) + _varint(7) + _len(2, b"Hlo Proto"))
    plane = (_len(2, b"/host:metadata") + _len(4, event_md)
             + _len(5, stat_md))
    other = _len(2, b"/device:TPU:0")
    xspace = _len(1, other) + _len(1, plane)
    assert parts.op_names(xspace) == {"fusion.1": NESTED["fusion.1"]}
    assert parts.op_names(_len(1, other)) == {}


@pytest.fixture(scope="module")
def recorded_pd(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "pd_grid16.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "pd_grid16.xplane.pb.gz").read_bytes()))
    maps = json.loads(gzip.decompress(
        (DATA / "pd_grid16.ops.json.gz").read_bytes()))
    return path, tr.load_trace(path), maps["phases"], maps["parts"]


def test_recorded_pd_trace_reduces_to_parts(recorded_pd):
    """Two pd rounds of a 16 x 16 grid (2,048 edge slots, sparse path,
    short cap 4) on one TPU v5e, with the part scopes; the recording is
    cut to the device's op line, the host's lines and the solve's
    HloProto (instruction names and op names), events without their
    stats. The op names in the trace give the phases that the program's
    text gave (``pd_grid16.ops.json``, from ``op_phases``); every part
    has time, and the parts of each phase add up to it."""
    path, (ops, host), phases, text_parts = recorded_pd
    assert len(ops) == 26333
    names = parts.op_names(path.read_bytes())
    by_part = parts.op_parts(names)
    assert {k: v.split(".")[0] for k, v in by_part.items()} == phases
    assert by_part == text_parts
    red = tr.reduce_events(ops, host, phases)
    assert red.phase_s == pytest.approx(
        {"separation": 0.049094330, "contraction": 0.012326275,
         "message_passing": 0.007943615, "loop": 0.000309309}, rel=1e-6)
    part_s = parts.part_seconds(ops, by_part)
    assert part_s["contraction.forest"] == pytest.approx(0.009047641,
                                                         rel=1e-6)
    named = set(PART_METRICS)
    assert named <= set(part_s)
    assert all(part_s[p] > 0 for p in named)
    assert set(part_s) <= named | {f"{p}.other" for p in red.phase_s}
    for phase, t in red.phase_s.items():
        if phase != "loop":
            assert sum(v for p, v in part_s.items()
                       if p.split(".")[0] == phase) == pytest.approx(
                t, rel=1e-12)


def _root_with_trace(tmp_path, fixture: str):
    """A checkout with the metric readers and ``fixture`` as the traced
    run's trace."""
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "bench/metrics")
    trace = tmp_path / harness.TRACE_DIR / "plugins/profile/run"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(gzip.decompress(
        (DATA / fixture).read_bytes()))
    return tmp_path


def _ctx(rounds):
    return tr.MetricContext(phase_s={"separation": 1.0}, busy_s=1.0,
                            window_s=1.0, rounds=rounds, work={},
                            device_kind="TPU v5 lite")


@pytest.mark.parametrize("part", PART_METRICS)
def test_part_metric_reads_the_recorded_pd_trace(tmp_path, part,
                                                 recorded_pd):
    root = _root_with_trace(tmp_path, "pd_grid16.xplane.pb.gz")
    path, (ops, _), _, _ = recorded_pd
    want = parts.part_seconds(ops, parts.op_parts(
        parts.op_names(path.read_bytes())))[part]
    read = harness.metric_reader(root, f"{part}.device_ms")
    assert read(_ctx(2)) == pytest.approx(1e3 * want / 2, rel=1e-12)
    assert read(_ctx(0)) is None


@pytest.mark.parametrize("part", PART_METRICS)
def test_part_metric_reads_nothing_without_part_scopes(tmp_path, part):
    """The d fixture was recorded from a program with phase scopes alone:
    each phase's time is ``<phase>.other``, and no reader raises."""
    read = harness.metric_reader(
        _root_with_trace(tmp_path, "d_grid16.xplane.pb.gz"),
        f"{part}.device_ms")
    assert read(_ctx(1)) is None


def test_reduce_dir_needs_one_trace(tmp_path):
    assert parts.reduce_dir(tmp_path / "none") == {}
    root = _root_with_trace(tmp_path, "pd_grid16.xplane.pb.gz")
    trace_dir = root / harness.TRACE_DIR
    assert parts.reduce_dir(trace_dir)["contraction.forest"] > 0
    shutil.copy(trace_dir / "plugins/profile/run/host.xplane.pb",
                trace_dir / "plugins/profile/run/other.xplane.pb")
    assert parts.reduce_dir(trace_dir) == {}
