"""One run of one benchmark cell: set-up, the measured window, the check
of every answer, and, in a traced run, the per-layer metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by the name
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration as it is run, with
  its generator ``bench/configs/<config>.py`` (``generate(cfg, seed)``);
- ``bench/traffic/<traffic>.json``: the generator seeds of the cell's
  instances, the solver preset and backend, and the solver settings the
  preset must have;
- ``bench/metrics/<metric>.py``: ``read(ctx) -> float | None`` of one
  per-layer metric from the reduced device trace;
- ``bench/limits/<workload>.json``: the limits of the cell's check.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import check, reference, trace_reduce, work
from bench.host import HostInstance, solve_order

CACHE_DIR = Path("bench") / ".jax_cache"    # under the checkout's root
TRACE_DIR = Path("bench") / ".trace"


class NoChip(RuntimeError):
    """The devices JAX found are not the ones the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    chips: int
    config: dict
    traffic: dict
    generate: object            # generate(cfg, seed) -> HostInstance
    end_to_end: list            # metric entries of BENCHMARK.json
    per_layer: list


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    w = _by_name(spec["workloads"], workload, "workload")
    c = _by_name(spec["configs"], w["config"], "configuration")
    cfg_file = root / c["file"]
    return Cell(
        chips=w["chips"],
        config=json.loads(cfg_file.read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        generate=load_module(cfg_file.with_suffix(".py"),
                             f"bench_config_{c['name']}").generate,
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, workload)])


def metric_reader(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    return load_module(path, "bench_metric_" + name.replace(".", "_")).read


def device_gate(chips: int) -> dict:
    """The devices of the run: ``chips`` TPUs or more, else NoChip."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = root / CACHE_DIR
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))


class CompileCounter:
    """Counts JAX's tracing, lowering and compiling events."""

    def __init__(self):
        import jax
        self.n = 0

        def on_event(event: str, duration: float, **kw) -> None:
            if event.startswith("/jax/core/compile/"):
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def make_solver(traffic: dict):
    """``solve(inst)`` through ``repro.api.solve`` with the traffic's
    preset and backend, after checking the preset's settings."""
    from repro import api
    preset = api.get_preset(traffic["preset"])
    for key, want in traffic["solver"].items():
        have = getattr(preset.config, key)
        if have != want:
            raise ValueError(f"preset {preset.name!r} has {key}={have!r}; "
                             f"the traffic file pins {want!r}")

    def solve(inst):
        return api.solve(inst, preset=traffic["preset"],
                         backend=traffic["backend"])
    solve.mode = preset.mode
    return solve


@dataclasses.dataclass
class Answer:
    instance: int               # index into the run's instances
    labels: np.ndarray
    objective: float
    lower_bound: float
    rounds: int
    seconds: float
    parts: tuple = ()           # seconds of transfer, dispatch, readback


def solve_once(solve, hi: HostInstance, index: int) -> Answer:
    """One solve from host arrays to the answer on the host."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core.graph import MulticutInstance
    t0 = time.perf_counter()
    with TraceAnnotation("bench.transfer"):
        inst = MulticutInstance(*jax.device_put(
            (hi.u, hi.v, hi.cost, hi.edge_valid, hi.node_valid)))
    t1 = time.perf_counter()
    with TraceAnnotation("bench.dispatch"):
        res = solve(inst)
    t2 = time.perf_counter()
    with TraceAnnotation("bench.readback"):
        labels, obj, lb, rounds = jax.device_get(
            (res.labels, res.objective, res.lower_bound, res.rounds))
    t3 = time.perf_counter()
    return Answer(index, np.asarray(labels), float(obj), float(lb),
                  int(rounds), t3 - t0, (t1 - t0, t2 - t1, t3 - t2))


def warm_instance(hi: HostInstance) -> HostInstance:
    """The same padded shapes with every cost negative: nothing
    contracts, so a solve of it stops after round 0 and compiles or loads
    the one executable the window uses."""
    cost = np.where(hi.edge_valid, -np.abs(hi.cost) - 1.0, 0.0)
    return dataclasses.replace(hi, cost=cost.astype(np.float32))


def degree_line(k: int, hi: HostInstance) -> str:
    u, v, c = hi.valid_edges()
    n = hi.num_nodes
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    pos = c > 0
    att = np.bincount(np.concatenate([u[pos], v[pos]]), minlength=n)
    return (f"instance {k}: nodes {n} edges {len(u)} padded {hi.num_edges}"
            f" degree mean {deg.mean():.2f} p95 {np.percentile(deg, 95):.0f}"
            f" max {deg.max()} above16 {(deg > 16).mean():.4f}"
            f" attractive p95 {np.percentile(att, 95):.0f} max {att.max()}"
            f" above16 {(att > 16).mean():.4f}"
            f" histogram {np.bincount(deg).tolist()}")


def cycles45_rounds(mode: str, solver: dict, rounds: int) -> list[bool]:
    """Per round, whether it separates 4- and 5-cycles (mode d: every
    round; pd: the first, where ``first_round_cycles45``)."""
    if mode == "d":
        return [True] * rounds
    first = solver["first_round_cycles45"] or solver["always_cycles45"]
    return [first] + [solver["always_cycles45"]] * (rounds - 1)


def phase_work(mode: str, solver: dict, hi: HostInstance,
               rounds: int) -> dict:
    """{phase: (operations, bytes)} of one solve of ``rounds`` rounds."""
    E, N = hi.num_edges, hi.num_nodes
    mp = [work.message_passing(E, N, solver, c45)
          for c45 in cycles45_rounds(mode, solver, rounds)]
    out = {"message_passing": tuple(map(sum, zip(*mp)))}
    if mode != "d":
        f, b = work.contraction(E, N, solver)
        out["contraction"] = (f * rounds, b * rounds)
    return out


def _finite(x):
    return x if isinstance(x, int) or math.isfinite(x) else str(x)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             traced: bool, t_start: float, gate: bool = True,
             solve=None) -> dict:
    """One run of ``workload``; returns the result line's object.

    ``gate=False`` skips the look for a chip and ``solve`` replaces the
    program's solve: tests drive a run on the CPU with both."""
    import jax
    cell = load_cell(root, workload)
    lim = check.limits(root, workload)
    if gate:
        device = device_gate(cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    enable_compile_cache(root)
    cfg, traffic = cell.config, cell.traffic
    solve = make_solver(traffic) if solve is None else solve
    mode = getattr(solve, "mode", "pd")
    instances = [cell.generate(cfg, s) for s in traffic["instance_seeds"]]
    for k, hi in enumerate(instances):
        log(degree_line(k, hi))
    order = solve_order(seed, len(instances))
    solve_once(solve, warm_instance(instances[0]), -1)
    gc.collect()        # what compiling left behind, before the window
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    counter = CompileCounter()
    answers = []
    trace_dir = root / TRACE_DIR
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t0 = time.perf_counter()
    while not answers or time.perf_counter() - t0 < seconds:
        k = order[len(answers) % len(instances)]
        with jax.profiler.TraceAnnotation("bench.solve"):
            answers.append(solve_once(solve, instances[k], k))
        if traced and len(answers) == len(instances):
            jax.profiler.stop_trace()
    window_s = time.perf_counter() - t0
    if traced and len(answers) < len(instances):
        jax.profiler.stop_trace()
    compiles = counter.n
    dev0 = jax.devices()[0]
    stats = dev0.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = peak
    log(f"window {window_s:.3f} s, {len(answers)} solves, "
        f"{compiles} compile events, peak_bytes_in_use {peak}")
    for a in answers:
        log(f"solve {a.instance}: {a.seconds:.4f} s rounds {a.rounds} "
            f"objective {a.objective!r} lower bound {a.lower_bound!r} "
            f"transfer/dispatch/readback "
            f"{' '.join(f'{x:.4f}' for x in a.parts)}")

    trivial = [reference.trivial_bound(hi) for hi in instances]
    planted = [reference.planted_objective(hi) for hi in instances]
    per_solve = [check.solve_numbers(instances[a.instance], a.labels,
                                     a.objective, a.lower_bound,
                                     trivial[a.instance],
                                     planted[a.instance])
                 for a in answers]
    correct, failed, table = check.judge(per_solve, compiles, lim)

    result = {"correct": correct, "attempted": len(answers),
              "failed": failed}
    if traced:
        metrics, breakdown, busy_s, span_s = traced_metrics(
            root, cell, mode, instances,
            answers[:len(instances)], device["kind"])
        device["busy_s"], device["window_s"] = busy_s, span_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = breakdown
    else:
        values = end_to_end_values(answers, trivial, setup_s, window_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": _finite(v["value"]),
                            "limit": v["limit"]} for k, v in table.items()}
    for k, v in table.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return result


def end_to_end_values(answers, trivial, setup_s, window_s) -> dict:
    """setup_s, solve_s, and the means over the run's instances of the
    two (repeatable) per-instance qualities: the certified gap and the
    bound's lift over the zero dual's."""
    by_inst = {}
    for a in answers:
        by_inst.setdefault(a.instance, a)
    gaps = [(a.objective - a.lower_bound) / abs(a.lower_bound)
            for a in by_inst.values()]
    lifts = [(a.lower_bound - trivial[i]) / abs(trivial[i])
             for i, a in by_inst.items()]
    return {"setup_s": setup_s, "solve_s": window_s / len(answers),
            "gap_rel": float(np.mean(gaps)),
            "bound_lift": float(np.mean(lifts))}


def traced_metrics(root, cell, mode, instances, answers, kind):
    """Per-layer metrics, breakdown, busy and window seconds of the
    traced solves (the run's first pass over its instances)."""
    from repro import api
    compiled = api.compiled_solve(
        preset=cell.traffic["preset"], backend=cell.traffic["backend"])
    from repro.core.graph import MulticutInstance
    hi = instances[0]
    hlo = compiled.lower(MulticutInstance(
        hi.u, hi.v, hi.cost, hi.edge_valid, hi.node_valid)).compile() \
        .as_text()
    red = trace_reduce.reduce_dir(root / TRACE_DIR,
                                  trace_reduce.op_phases(hlo))
    totals = {}
    for a in answers:
        for ph, (f, b) in phase_work(mode, cell.traffic["solver"],
                                     instances[a.instance],
                                     a.rounds).items():
            f0, b0 = totals.get(ph, (0.0, 0.0))
            totals[ph] = (f0 + f, b0 + b)
    ctx = trace_reduce.MetricContext(
        phase_s=red.phase_s, busy_s=red.busy_s, window_s=red.window_s,
        rounds=sum(a.rounds for a in answers), work=totals,
        device_kind=kind)
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log("phase device seconds: " + json.dumps(red.phase_s))
    return metrics, red.breakdown(), red.busy_s, red.window_s
