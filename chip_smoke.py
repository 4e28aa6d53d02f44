#!/usr/bin/env python3
"""Chip smoke test: RAMA's main path, once, on one TPU chip.

    python chip_smoke.py                # one chip: solve + serve phases
    python chip_smoke.py --four-chips   # state_shards=4 vs 1, four chips

Everything runs in this one process (a chip belongs to one process at a
time) on data generated from ``--seed``. Phases:

1. device gate: the first JAX device must be a TPU; anything else exits
   non-zero before any work is done;
2. compile cache: :func:`repro.compile_cache.enable_compile_cache`;
3. solve, through :func:`repro.api.solve`: mode ``pd`` on
   ``grid_instance(1024, 1024)`` (1,048,576 nodes, 5,226,505 edges, the
   Cityscapes-regime stand-in of RAMA Table 1) with both kernel backends
   and ``BIG_ROUNDS`` solver rounds, and modes ``p``/``pd``/``pd+``/``d`` on ``grid_instance(256, 256)``.
   Every run is checked: rounds >= 1, the objective recomputed on the host
   from the labels, a finite lower bound that is at most the objective,
   pallas against reference, and Mosaic kernels (``tpu_custom_call``) in
   the pallas executable;
4. serve: a :class:`repro.serve.SolveEngine` serves 16 segmentation tiles
   of two sizes, each result bit-identical to ``api.solve`` of the
   bucket-padded tile, and one sticky session takes two exact delta ticks.

A cold compile of one solve takes minutes, so the six solve executables
compile at once, one thread each, while the serve phase runs in one more
thread; the solves then run one at a time as the serve phase finishes.

``--four-chips`` runs only the edge-partitioned solve: ``state_shards=4``
against ``state_shards=1`` on ``grid_instance(2048, 2048)`` (``BIG_ROUNDS``
rounds), labels,
objective and lower bound bitwise equal, on exactly four devices.

The last line of stdout is ``{"ok": true, "device": {...}}``. A failed
check raises, so the script exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
SRC = REPO / "src"

BIG_SIDE = 1024         # 1,048,576 nodes, 5,226,505 edges
# Solver rounds on the 1024 and 2048 grids, cut from SolverConfig's 16:
# a round of pd on the 1024 grid takes 18-22 s on one v5e, so two full
# solves alone would fill 640 s of the run's 1,200 s. The instance stays
# at full size; every round runs at the same padded shapes.
BIG_ROUNDS = 4
SMALL_SIDE = 256        # p / pd+ / d, and the pd objective d is held to
TILE_SIDES = (64, 128)  # serve phase: two tile sizes, two buckets
TILES_PER_SIDE = 8      # = batch_cap: each bucket dispatches one full batch
DELTA_TICKS = 2         # ticks of the sticky session
FOUR_CHIP_SIDE = 2048   # ~20.9M edges
FOUR_CHIP_SHARDS = 4

OBJ_RTOL = 1e-4         # device objective vs host recomputation
BACKEND_RTOL = 1e-3     # pallas vs reference, objective and lower bound


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


_T0 = time.perf_counter()


def progress(msg: str) -> None:
    """A line stamped with the seconds since the script started, so a run
    cut by its time limit still shows where the time went."""
    log(f"[{time.perf_counter() - _T0:7.1f} s] {msg}")


def finite_or_none(x: float) -> float | None:
    """Strict JSON has no infinities: the bound a mode does not produce
    (``p``'s lower bound, ``d``'s objective) is reported as null."""
    return x if math.isfinite(x) else None


def rel_close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * max(1.0, abs(a), abs(b))


def device_gate() -> dict:
    """The device the run is for: a TPU, or exit non-zero."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax.devices()[0].platform="
                         f"{d.platform!r}); refusing to run on {d.platform}")
    return info


def peak_bytes(device) -> int | None:
    """``peak_bytes_in_use`` of ``device`` (None where the backend keeps
    no memory statistics, as the CPU backend does)."""
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def check_kernels(label: str, compiled_text: str) -> None:
    """The pallas executable must hold Mosaic kernels: without
    ``tpu_custom_call`` the kernels were lowered through the Pallas
    interpreter instead."""
    check("tpu_custom_call" in compiled_text,
          f"{label}: no tpu_custom_call in the compiled executable; the "
          f"Pallas kernels fell back to interpret mode")


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_BACKEND_COMPILES: dict[int, int] = {}
_COUNTING = []


def backend_compiles() -> int:
    """XLA compilations by the calling thread since the first call (loads
    from the persistent cache included), counted from JAX's compile event,
    which fires in the thread that compiles. Per thread, so the serve
    phase compiling alongside does not count against a solve."""
    if not _COUNTING:
        import jax
        _COUNTING.append(True)

        def count(event: str, duration: float, **kwargs) -> None:
            if event == BACKEND_COMPILE_EVENT:
                tid = threading.get_ident()
                _BACKEND_COMPILES[tid] = _BACKEND_COMPILES.get(tid, 0) + 1

        jax.monitoring.register_event_duration_secs_listener(count)
    return _BACKEND_COMPILES.get(threading.get_ident(), 0)


def lower_solve(inst, mode: str, backend: str = "reference",
                config=None):
    """Trace and lower the ``api.solve`` executable of ``inst`` (the
    registry entry ``api.solve`` dispatches to), without compiling it.
    ``config`` is the ``SolverConfig`` (None: the default)."""
    from repro import api
    return api.compiled_solve(mode, config, backend).lower(inst)


def compile_all(lowered: dict) -> dict:
    """Compile every lowered executable at once, one thread each.

    A cold TPU compile of one solve takes minutes and mostly one core, so
    compiling the executables one after another would make compilation
    most of the run. XLA compiles without the GIL. Returns ``{label:
    (compiled, compile_s)}``; each ``compile_s`` overlaps the others. An
    exception in any thread is raised here."""
    from concurrent.futures import ThreadPoolExecutor

    def timed(label, low):
        t0 = time.perf_counter()
        compiled = low.compile()
        seconds = time.perf_counter() - t0
        progress(f"compiled {label} in {seconds:.1f} s")
        return compiled, seconds

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowered)) as pool:
        futures = {label: pool.submit(timed, label, low)
                   for label, low in lowered.items()}
        out = {label: f.result() for label, f in futures.items()}
    log(f"compile: {len(lowered)} executables in "
        f"{time.perf_counter() - t0:.1f} s, concurrently")
    return out


def run_solve(label: str, inst, mode: str, backend: str, compiled,
              pd_objective: float | None = None, config=None) -> dict:
    """Run and check one ``api.solve`` executable; print its line.

    ``compiled`` is ``(compiled executable, compile_s)`` from
    :func:`compile_all`; the call must reuse it, so its wall is a warm
    one. ``pd_objective`` is the ``pd`` objective of the same instance,
    the bound a ``d`` lower bound is held to; ``config`` is the one the
    executable was lowered with."""
    import jax
    import numpy as np

    from repro import api
    from repro.core.baselines import objective

    executable, compile_s = compiled
    if backend == "pallas":
        check_kernels(label, executable.as_text())
    progress(f"running {label}")
    compiles0 = backend_compiles()
    t0 = time.perf_counter()
    res = jax.block_until_ready(api.solve(inst, mode=mode, config=config,
                                          backend=backend,
                                          graph_impl="auto"))
    warm_s = time.perf_counter() - t0
    check(backend_compiles() == compiles0,
          f"{label}: api.solve compiled again instead of reusing the "
          f"compiled executable")

    rounds = int(res.rounds)
    obj = float(res.objective)
    lb = float(res.lower_bound)
    check(rounds >= 1, f"{label}: ran {rounds} rounds")
    if mode != "d":
        host = objective(inst, np.asarray(res.labels))
        check(rel_close(obj, host, OBJ_RTOL),
              f"{label}: device objective {obj!r} != host {host!r}")
    if mode != "p":
        bound = obj if mode != "d" else pd_objective
        check(math.isfinite(lb), f"{label}: lower bound {lb!r}")
        check(bound is not None and lb <= bound,
              f"{label}: lower bound {lb!r} exceeds objective {bound!r}")
    gap = obj - lb if mode in ("pd", "pd+") else None
    rec = {"label": label, "mode": mode, "backend": backend,
           "nodes": inst.num_nodes, "edges": inst.num_edges,
           "compile_s": compile_s, "warm_s": warm_s,
           "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
           "rounds": rounds, "objective": finite_or_none(obj),
           "lower_bound": finite_or_none(lb),
           "gap": gap,
           "rel_gap": None if gap is None else gap / max(abs(lb), 1e-30)}
    log("solve " + json.dumps(rec))
    return rec


def solve_phase(big_side: int = BIG_SIDE, small_side: int = SMALL_SIDE,
                seed: int = 0, alongside=None) -> list[dict]:
    """``pd`` at real size on both backends (``BIG_ROUNDS`` rounds), the
    other modes on the small grid (default config). ``alongside`` (the serve phase, in a full run; a callable or
    None) runs in its own thread from the start. All six executables
    compile at once meanwhile; the solves then run one after another
    while ``alongside`` finishes, so a solve's wall may include a serve
    tile's turn on the chip. Every executable is traced before the serve
    thread starts, so nothing here traces while the engine counts its own
    traces. A failure of either side is raised here."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.graph import grid_instance
    from repro.core.solver import SolverConfig

    big = grid_instance(big_side, big_side, seed=seed)
    small = grid_instance(small_side, small_side, seed=seed)
    big_cfg = SolverConfig(max_rounds=BIG_ROUNDS)
    jobs = {f"grid{big_side}/pd/{backend}": (big, "pd", backend, big_cfg)
            for backend in ("reference", "pallas")}
    jobs.update({f"grid{small_side}/{mode}": (small, mode, "reference", None)
                 for mode in ("pd", "p", "pd+", "d")})
    lowered = {label: lower_solve(*job) for label, job in jobs.items()}

    recs = {}
    pd_objective = None
    with ThreadPoolExecutor(1) as pool:
        side = None if alongside is None else pool.submit(alongside)
        compiled = compile_all(lowered)
        for label, (inst, mode, backend, cfg) in jobs.items():
            if side is not None and side.done():
                side.result()           # a failed serve phase stops the run
            recs[label] = run_solve(label, inst, mode, backend,
                                    compiled.pop(label), pd_objective, cfg)
            if inst is small and mode == "pd":
                pd_objective = recs[label]["objective"]
        if side is not None:
            side.result()
    ref = recs[f"grid{big_side}/pd/reference"]
    pal = recs[f"grid{big_side}/pd/pallas"]
    for key in ("objective", "lower_bound"):
        check(rel_close(pal[key], ref[key], BACKEND_RTOL),
              f"grid{big_side}/pd: pallas {key} {pal[key]!r} vs reference "
              f"{ref[key]!r}")
    return list(recs.values())


def _bit_eq(a, b) -> bool:
    import numpy as np
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _mismatch(got, ref, n: int) -> str | None:
    """None if ``got`` equals ``ref`` bitwise in objective, lower bound
    and the first ``n`` labels; else which of them differ, with values."""
    import numpy as np
    differ = [k for k, a, b in (
        ("objective", got.objective, ref.objective),
        ("lower_bound", got.lower_bound, ref.lower_bound),
        ("labels", got.labels, np.asarray(ref.labels)[:n]))
        if not _bit_eq(a, b)]
    if not differ:
        return None
    return (f"{differ} differ: objective {float(got.objective)!r} vs "
            f"{float(ref.objective)!r}, lower bound "
            f"{float(got.lower_bound)!r} vs {float(ref.lower_bound)!r}")


def _tick_patch(inst, tick: int):
    """Re-weight one valid edge of ``inst`` (which edge depends on the
    tick), as a frame-to-frame cost update would."""
    import numpy as np

    from repro import api
    ev = np.asarray(inst.edge_valid)
    u = np.asarray(inst.u)[ev]
    v = np.asarray(inst.v)[ev]
    i = (7919 * (tick + 1)) % len(u)
    return api.make_patch(inst.num_nodes,
                          reweight=([int(u[i])], [int(v[i])],
                                    [2.0 + tick]))


def serve_phase(tile_sides=TILE_SIDES, tiles_per_side: int = TILES_PER_SIDE,
                seed: int = 0) -> dict:
    """Mixed-size tiles through the engine, then one sticky session."""
    import jax
    import numpy as np

    from repro import api
    from repro.core.graph import grid_instance
    from repro.incremental import apply_patch_host
    from repro.serve import (
        BucketPolicy, SolveEngine, default_router, pad_instance,
    )

    rng = np.random.default_rng(seed)
    sides = [s for s in tile_sides for _ in range(tiles_per_side)]
    rng.shuffle(sides)
    tiles = [grid_instance(s, s, seed=int(rng.integers(1 << 30)),
                           n_segments=3, pad_edges=5 * s * s)
             for s in sides]
    engine = SolveEngine(router=default_router(),
                         policy=BucketPolicy(node_floor=64, edge_floor=256),
                         batch_cap=tiles_per_side, flush_timeout_s=None)

    progress(f"serve: {len(tiles)} tiles, cold")
    t0 = time.perf_counter()
    tickets = engine.submit_many(tiles)
    # The stream is in flight. Compile the executables of the direct
    # solves checked below meanwhile: in a full run the stream waits for
    # the chip behind the 1024-grid solves.
    for t in {(t.bucket, t.route): t for t in tickets}.values():
        api.compiled_solve(t.route.mode, t.route.config,
                           t.route.backend).lower(
            pad_instance(t.inst, t.bucket)).compile()
    results = [t.result() for t in tickets]
    cold_s = time.perf_counter() - t0
    progress(f"serve: cold stream in {cold_s:.1f} s (with the direct "
             f"solves' compiles)")
    compiles = engine.stats.compiles
    t0 = time.perf_counter()
    warm_results = engine.solve_stream(tiles)
    warm_s = time.perf_counter() - t0
    check(engine.stats.compiles == compiles,
          f"serve: warm stream compiled "
          f"{engine.stats.compiles - compiles} more executables")

    for i, (t, r, w) in enumerate(zip(tickets, results, warm_results)):
        direct = api.solve(pad_instance(t.inst, t.bucket), mode=t.route.mode,
                           config=t.route.config, backend=t.route.backend)
        n = t.inst.num_nodes
        for got in (r, w):
            bad = _mismatch(got, direct, n)
            check(bad is None,
                  f"serve: tile {i} ({sides[i]}px) differs from api.solve "
                  f"of its bucket-padded instance: {bad}")

    progress("serve: tiles match api.solve; opening a session")
    inst = tiles[0]
    session = engine.open_session(inst, warm=False)
    padded = pad_instance(inst, session.bucket)
    tick_s = []
    for tick in range(DELTA_TICKS):
        patch = _tick_patch(inst, tick)
        t0 = time.perf_counter()
        res = jax.block_until_ready(
            engine.submit_delta(session.session_id, patch).result())
        tick_s.append(time.perf_counter() - t0)
        padded = apply_patch_host(padded, patch)
        cold = api.solve(padded, mode=session.route.mode,
                         config=session.route.config,
                         backend=session.route.backend)
        bad = _mismatch(res, cold, inst.num_nodes)
        check(bad is None,
              f"serve: session tick {tick} differs from api.solve of the "
              f"patched padded instance: {bad}")
    rec = {"tiles": len(tiles), "tile_sides": list(tile_sides),
           "cold_stream_s": cold_s, "warm_stream_s": warm_s,
           "stream_compiles": compiles,
           "compiles": engine.stats.compiles,
           "delta_tick_s": tick_s,
           "peak_bytes_in_use": peak_bytes(jax.devices()[0])}
    log("serve " + json.dumps(rec))
    return rec


def four_chip_phase(side: int = FOUR_CHIP_SIDE, seed: int = 0) -> dict:
    """The edge-partitioned solve on four devices against one shard:
    labels, objective and lower bound bitwise equal. A clamp of the shard
    count fails the phase instead of shrinking the run."""
    import dataclasses

    import jax
    import numpy as np

    from repro import api
    from repro.core.dist import resolve_state_shards
    from repro.core.graph import (
        grid_instance, make_instance, round_up_edges, to_host_edges,
    )
    from repro.core.solver import SolverConfig

    shards = FOUR_CHIP_SHARDS
    check(jax.device_count() == shards,
          f"four-chip: {jax.device_count()} devices, need {shards}")
    check(resolve_state_shards(shards) == shards,
          f"four-chip: state_shards={shards} resolves to "
          f"{resolve_state_shards(shards)}")
    u, v, c = to_host_edges(grid_instance(side, side, seed=seed))
    inst = make_instance(u, v, c, side * side,
                         pad_edges=round_up_edges(len(u), shards))
    base = SolverConfig(graph_impl="sparse", first_round_cycles45=False,
                        max_rounds=BIG_ROUNDS)
    out = {"nodes": inst.num_nodes, "edges": len(u),
           "pad_edges": inst.num_edges}
    configs = {s: dataclasses.replace(base, state_shards=s)
               for s in (shards, 1)}
    compiled = compile_all({s: api.compiled_solve("pd", cfg).lower(inst)
                            for s, cfg in configs.items()})
    results = {}
    # the sharded run goes first: peak_bytes_in_use only grows, and the
    # replicated run afterwards raises device 0's peak alone
    for s, cfg in configs.items():
        compile_s = compiled.pop(s)[1]
        t0 = time.perf_counter()
        res = jax.block_until_ready(api.solve(inst, mode="pd", config=cfg))
        warm_s = time.perf_counter() - t0
        check(int(res.rounds) >= 1, f"four-chip S={s}: no rounds")
        results[s] = res
        out[f"state_shards={s}"] = {
            "compile_s": compile_s, "warm_s": warm_s,
            "rounds": int(res.rounds), "objective": float(res.objective),
            "lower_bound": float(res.lower_bound),
            "peak_bytes_in_use": [peak_bytes(d) for d in jax.devices()]}
    a, b = results[shards], results[1]
    same = {k: _bit_eq(getattr(a, k), getattr(b, k))
            for k in ("labels", "objective", "lower_bound")}
    out["bitwise_equal"] = same
    log("four-chip " + json.dumps(out))
    check(all(same.values()),
          f"four-chip: state_shards={shards} differs from state_shards=1: "
          f"{same}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the state_shards=4 vs 1 comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {SRC}; run "
                         f"this script from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    device = device_gate()

    import jax

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cache = {"cache_hits": 0, "cache_misses": 0}

    def count(event: str, **kwargs) -> None:
        name = event.rsplit("/", 1)[-1]
        if name in cache:
            cache[name] += 1

    jax.monitoring.register_event_listener(count)
    if args.four_chips:
        four_chip_phase(seed=args.seed)
    else:
        solve_phase(seed=args.seed,
                    alongside=lambda: serve_phase(seed=args.seed))
    log(f"compile cache: {cache['cache_hits']} hits, "
        f"{cache['cache_misses']} misses")
    log(f"total wall (compiles included): "
        f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
