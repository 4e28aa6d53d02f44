"""Reduction of a JAX profiler trace of whole solves to per-phase device
time, busy and idle time, and the breakdown.

The device plane's ``XLA Ops`` line holds one event per HLO instruction
run, named by the instruction's text (``%fusion.12 = ...``). Loops and
calls nest: a ``while`` event spans the events of its body. Each instant
of device time goes to the innermost event running then (its self
time), and each instruction to the solver phase whose named scope
(``repro.separation``, ``repro.message_passing``, ``repro.contraction``)
appears in its ``op_name`` metadata in the compiled program's text. An
instruction in no phase scope counts as loop overhead (``loop``).

Host spans that the harness writes (``bench.transfer``,
``bench.dispatch``, ``bench.readback`` inside each ``bench.solve``) lie
on the same clock and name what the host did in each idle gap."""
from __future__ import annotations

import dataclasses
import heapq
import re
from collections import Counter
from pathlib import Path

from bench import peaks

PHASE_RE = re.compile(r"repro\.(separation|message_passing|contraction)")
INSTR_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')
HOST_SPANS = ("bench.transfer", "bench.dispatch", "bench.readback")
DEVICE_PLANE = "/device:TPU:0"


def op_phases(hlo_text: str) -> dict:
    """{instruction name: phase} of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR_RE.match(line)
        if m:
            p = PHASE_RE.search(m.group(2))
            out[m.group(1)] = p.group(1) if p else "loop"
    return out


def op_name(event_name: str) -> str:
    """``fusion.12`` of an event named ``%fusion.12 = f32[...] ...``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list) -> Counter:
    """{op: self seconds}: each instant of the union of ``ops`` ((start_s,
    end_s, name) tuples) goes to the latest-started event still running."""
    bounds = sorted([(s, 1, i) for i, (s, e, _) in enumerate(ops)]
                    + [(e, 0, i) for i, (s, e, _) in enumerate(ops)])
    active = []          # max-heap on start: (-start, -index)
    done = set()
    out = Counter()
    last = None
    for t, is_start, i in bounds:
        while active and -active[0][1] in done:
            heapq.heappop(active)
        if active and last is not None and t > last:
            out[ops[-active[0][1]][2]] += t - last
        last = t
        if is_start:
            heapq.heappush(active, (-ops[i][0], -i))
        else:
            done.add(i)
    return out


def union(intervals: list) -> list:
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduction:
    phase_s: dict           # phase -> device self seconds
    busy_s: float           # union of the device ops' intervals
    window_s: float         # the traced solves, by host and device clock
    top_ops: list           # [(phase/op, seconds)], longest first
    idle_gaps: list         # [(host activity, seconds)], longest first

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops[:10]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:10]]}


def reduce_events(ops: list, host: list, phases: dict) -> Reduction:
    """``ops``: device (start_s, end_s, event name); ``host``: host spans
    (start_s, end_s, name), among them ``bench.solve``."""
    solves = [(s, e) for s, e, n in host if n == "bench.solve"]
    ops = [(s, e, op_name(n)) for s, e, n in ops]
    # the trace holds the traced solves' ops alone; the host's and the
    # device's clocks can differ by a fraction of a millisecond, so the
    # window spans both
    w0 = min([s for s, _ in solves] + [s for s, _, _ in ops])
    w1 = max([e for _, e in solves] + [e for _, e, _ in ops])
    own = self_times(ops)
    phase_s = Counter()
    per_op = Counter()
    for name, t in own.items():
        ph = phases.get(name, "loop")
        phase_s[ph] += t
        per_op[f"{ph}/{name}"] += t
    busy = union([(s, e) for s, e, _ in ops])
    busy_s = sum(e - s for s, e in busy)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    spans = [(s, e, n.split(".", 1)[1]) for s, e, n in host
             if n in HOST_SPANS]
    gaps = []
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        cover = Counter()
        for s, e, n in spans:
            cover[n] += max(0.0, min(e, g1) - max(s, g0))
        what = cover.most_common(1)[0][0] if cover and \
            cover.most_common(1)[0][1] > 0 else "other"
        gaps.append((what, g1 - g0))
    gaps.sort(key=lambda x: -x[1])
    return Reduction(phase_s=dict(phase_s), busy_s=busy_s,
                     window_s=w1 - w0, top_ops=per_op.most_common(),
                     idle_gaps=gaps)


def load_trace(path: Path) -> tuple[list, list]:
    """(device ops, host spans) of one ``.xplane.pb``, in seconds."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(path))
    ops, host = [], []
    for plane in prof.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                           for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, e.name)
                         for e in line.events if e.name.startswith("bench.")]
    return ops, host


def reduce_dir(trace_dir: Path, phases: dict) -> Reduction:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {trace_dir}, "
                                f"found {len(files)}")
    ops, host = load_trace(files[0])
    if not ops:
        raise ValueError(f"no device ops on {DEVICE_PLANE} in {files[0]}")
    return reduce_events(ops, host, phases)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader gets: the reduced trace of the
    traced solves, their solver rounds, and the least work of each phase
    over them (``bench/work.py``)."""
    phase_s: dict
    busy_s: float
    window_s: float
    rounds: int
    work: dict              # phase -> (operations, bytes)
    device_kind: str

    def per_round_ms(self, phase: str):
        t = self.phase_s.get(phase, 0.0)
        if t <= 0 or self.rounds <= 0:
            return None
        return 1e3 * t / self.rounds

    def roofline_share(self, phase: str):
        """Least time of the phase's work over its device time, in %."""
        t = self.phase_s.get(phase, 0.0)
        if t <= 0 or phase not in self.work:
            return None
        flops, nbytes = self.work[phase]
        return 100.0 * peaks.least_seconds(flops, nbytes,
                                           self.device_kind) / t
