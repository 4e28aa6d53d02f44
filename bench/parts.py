"""Device time of the parts of the solver's phases, read from the profiler
trace of a traced run.

Below its phase scopes the program names parts: ``repro.<phase>.<part>``
scopes nested inside ``repro.<phase>`` (``repro.contraction.forest``,
``repro.separation.long_bucket``). Parts nest, and an instruction belongs
to the innermost part of its phase; an instruction of a phase in no part
counts as ``<phase>.other``, so the parts of a phase partition its time.

The profiler keeps the compiled program (its ``HloProto``) in the
trace's ``/host:metadata`` plane, so the parts are read from the trace
file alone, from the same ``op_name`` metadata that
:func:`bench.trace_reduce.op_phases` reads from the program's text. The
time is the one :mod:`bench.trace_reduce` gives the phases: each instant
of device time goes to the innermost event running then, so the parts of
a phase add up to the phase's time. A program without part scopes has no
part: every reader then returns None. JAX ships no Python schema for
the trace's protobuf messages, so a decoder of a few lines reads the
handful of fields named below."""
from __future__ import annotations

import functools
import re
import sys
from collections import Counter
from pathlib import Path

from bench import trace_reduce
from bench.harness import TRACE_DIR

PART_RE = re.compile(
    r"repro\.(separation|message_passing|contraction)\.(\w+)")
HOST_METADATA = b"/host:metadata"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for varint
    and fixed fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _field(buf, number: int):
    """The last value of field ``number`` of one message, or None."""
    out = None
    for num, val in _fields(buf):
        if num == number:
            out = val
    return out


def _hlo_protos(xspace: bytes) -> list:
    """The serialized ``HloProto`` of each program in the trace: the
    ``Hlo Proto`` stats of the event metadata of the ``/host:metadata``
    plane.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entry:
    value = 2), stat_metadata = 5; XEventMetadata.stats = 5;
    XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1, bytes_value =
    6."""
    for num, pl in _fields(memoryview(xspace)):
        if num != 1 or bytes(_field(pl, 2) or b"") != HOST_METADATA:
            continue
        events, stat_names = [], {}
        for f, val in _fields(pl):
            if f == 4:
                events.append(_field(val, 2))
            elif f == 5:
                md = _field(val, 2)
                stat_names[_field(md, 1)] = bytes(_field(md, 2) or b"")
        hlo = {k for k, v in stat_names.items() if v == b"Hlo Proto"}
        protos = []
        for ev in events:
            for f, st in _fields(ev):
                if f == 5 and _field(st, 1) in hlo:
                    protos.append(_field(st, 6) or b"")
        return protos
    return []


def op_names(xspace: bytes) -> dict:
    """{instruction: op_name metadata} of the largest program in a
    serialized ``XSpace`` (the solve: the harness's trace holds nothing
    else that compiles), from the compiled module the profiler keeps
    beside the device's events. Instructions without an op_name are left
    out, as :func:`bench.trace_reduce.op_phases` leaves them out.

    HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto: name = 1,
    metadata = 7; OpMetadata.op_name = 2."""
    protos = _hlo_protos(xspace)
    if not protos:
        return {}
    module = _field(max(protos, key=len), 1)
    out = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name, meta = _field(instr, 1), _field(instr, 7)
            op = _field(meta, 2) if meta is not None else None
            if name is not None and op:
                out[bytes(name).decode()] = bytes(op).decode()
    return out


def op_parts(names: dict) -> dict:
    """{instruction: part}: ``<phase>.<part>`` of the innermost part scope
    of the instruction's phase in its op_name, ``<phase>.other`` in none,
    ``loop`` outside every phase."""
    out = {}
    for instr, op in names.items():
        p = trace_reduce.PHASE_RE.search(op)
        if p is None:
            out[instr] = "loop"
            continue
        parts = [part for phase, part in PART_RE.findall(op)
                 if phase == p.group(1)]
        out[instr] = f"{p.group(1)}.{parts[-1] if parts else 'other'}"
    return out


def part_seconds(ops: list, parts: dict) -> dict:
    """{part: device self seconds} of ``ops`` ((start_s, end_s, event
    name) tuples); instructions outside every phase are left out."""
    out = Counter()
    for name, t in trace_reduce.self_times(
            [(s, e, trace_reduce.op_name(n)) for s, e, n in ops]).items():
        part = parts.get(name, "loop")
        if part != "loop":
            out[part] += t
    return dict(out)


@functools.cache
def _reduce_file(path: str, mtime_ns: int, size: int) -> dict:
    ops, _ = trace_reduce.load_trace(Path(path))
    part_s = part_seconds(
        ops, op_parts(op_names(Path(path).read_bytes())))
    print("part device seconds: "
          + " ".join(f"{k} {v!r}" for k, v in sorted(part_s.items())),
          file=sys.stderr, flush=True)
    return part_s


def reduce_dir(trace_dir: Path) -> dict:
    """{part: device self seconds} of the one trace under ``trace_dir``;
    empty where there is none."""
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if len(files) != 1:
        return {}
    st = files[0].stat()
    return _reduce_file(str(files[0]), st.st_mtime_ns, st.st_size)


def per_round_ms(ctx, part: str, reader: str):
    """Device time of ``part`` per solver round, ms, from the trace of the
    checkout whose ``bench/metrics/`` holds ``reader``; None where the
    part has no time."""
    root = Path(reader).resolve().parents[2]
    t = reduce_dir(root / TRACE_DIR).get(part, 0.0)
    if t <= 0 or ctx.rounds <= 0:
        return None
    return 1e3 * t / ctx.rounds
