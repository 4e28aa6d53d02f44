"""Unified multicut solver API: one device-resident, vmap-able entrypoint.

All four paper variants (P / PD / PD+ / D) sit behind a single
:func:`solve` driven by :class:`SolverConfig`, with named presets and a
backend selector for the message-passing sweep:

    from repro import api

    res = api.solve(inst)                          # paper PD defaults
    res = api.solve(inst, mode="d")                # dual-only lower bound
    res = api.solve(inst, preset="pd-opt")         # named preset
    res = api.solve(inst, backend="pallas")        # kernel-backed MP sweep
    res = api.solve(inst, graph_impl="sparse")     # force the CSR data path

    mc = api.Multicut.from_preset("paper-pd+")
    res = mc.solve(inst)

    batch = api.stack_instances([inst0, inst1, ...])
    results = mc.solve_batch(batch)                # one vmapped executable

``graph_impl`` picks the separation data path ("dense" (N, N) MXU
matrices, "sparse" padded-CSR with O(N + E) memory, or "auto" — the
default — which flips to sparse above ``SolverConfig.sparse_threshold``
nodes). Every preset therefore scales past the dense ceiling untouched;
``"pd-sparse"`` pins the CSR path explicitly for benchmarking. On the
sparse path the solve carries a persistent ``SolverState`` (instance +
live CSR + mapping) through the round loop — the CSR is built once and
maintained by contraction — and ``SolverConfig.separation_chunk`` /
``separation_shards`` stream/shard the separation batch
(``"pd-chunked"`` / ``"pd-sharded"`` presets) with bit-identical results.

Every entrypoint returns a :class:`SolveResult` of device arrays — the
full solve (outer rounds included) is one compiled executable, and the
only host synchronisation happens when the caller reads the result.
Compiled callables live in a *bounded* LRU registry keyed per (mode,
config, backend, batched, batch_shards, kind) — :func:`compiled_solve`
exposes entries, :func:`clear_cache` / :func:`cache_info` /
:func:`set_cache_maxsize` manage it, and :func:`trace_count` counts the
XLA compilations that ran through it (the instrumentation
:mod:`repro.serve` uses to enforce its compile budget). Repeated solves
over same-shaped instances never retrace; ``solve_batch(batch_shards=N)``
shards the batch axis over the device mesh with bit-identical results.

Incremental solving (``kind != "solve"`` in the registry) rides the same
cache: :func:`solve_with_state` opens a :class:`DeltaState` around a cold
solve, and :func:`solve_delta` applies a :class:`DeltaPatch` and
re-solves — exactly (bit-identical to a cold solve of the patched
instance) or warm (``warm=True``: previous solution lifted, untouched
clusters pre-contracted, round-0 separation restricted to the patch
frontier). See :mod:`repro.incremental`.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.core.graph import GRAPH_IMPLS, MulticutInstance, make_instance
from repro.core.solver import (
    BACKENDS, MODES, SolveResult, SolverConfig, resolve_intersect,
    resolve_sweep, solve_device,
)
from repro.incremental.patch import (
    DeltaPatch, apply_patch_host, make_patch, pad_patch,
)
from repro.incremental.solve import solve_cold_device, solve_delta_device
from repro.incremental.state import DeltaState, init_delta_state

__all__ = [
    "BACKENDS", "CACHE_MAXSIZE", "GRAPH_IMPLS", "MODES", "DeltaPatch",
    "DeltaState", "Multicut", "MulticutInstance", "Preset", "PRESETS",
    "SolveResult", "SolverConfig", "apply_patch_host", "cache_info",
    "clear_cache",
    "compiled_delta", "compiled_solve", "get_preset", "init_delta_state",
    "list_presets", "make_instance", "make_patch", "pad_patch",
    "register_preset", "set_cache_maxsize", "solve", "solve_batch",
    "solve_delta", "solve_with_state", "stack_instances", "trace_count",
    "tree_ready", "unstack_results",
]


# ---------------------------------------------------------------------------
# Preset registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Preset:
    """A named (mode, config) pair. Frozen + hashable, like SolverConfig."""
    name: str
    mode: str
    config: SolverConfig
    description: str = ""


PRESETS: dict[str, Preset] = {}


def register_preset(preset: Preset, overwrite: bool = False) -> Preset:
    if preset.mode not in MODES:
        raise ValueError(f"preset {preset.name!r}: unknown mode "
                         f"{preset.mode!r}; expected one of {MODES}")
    if preset.name in PRESETS and not overwrite:
        raise ValueError(f"preset {preset.name!r} already registered")
    PRESETS[preset.name] = preset
    return preset


def get_preset(name: str) -> Preset:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown preset {name!r}; available: "
                       f"{sorted(PRESETS)}") from None


def list_presets() -> list[str]:
    return sorted(PRESETS)


_PAPER = SolverConfig()
for _p in (
    Preset("paper-p", "p", _PAPER,
           "purely primal contraction (paper's P)"),
    Preset("paper-pd", "pd", _PAPER,
           "interleaved primal-dual, 5-cycles on the original graph"),
    Preset("paper-pd+", "pd+", _PAPER,
           "primal-dual with 5-cycle separation every round"),
    Preset("paper-d", "d", _PAPER,
           "dual-only lower bound (paper's D)"),
    Preset("pd-opt", "pd",
           dataclasses.replace(_PAPER, contract_frac=0.5, max_rounds=40),
           "beyond-paper GAEC-conservative PD (contract_frac=0.5)"),
    Preset("pd-sparse", "pd",
           dataclasses.replace(_PAPER, graph_impl="sparse"),
           "PD pinned to the CSR data path (no (N, N) allocations)"),
    Preset("pd-chunked", "pd",
           dataclasses.replace(_PAPER, graph_impl="sparse",
                               separation_chunk=64),
           "CSR PD with chunked separation: peak separation memory bounded "
           "by separation_chunk, not max_neg (bit-identical results)"),
    Preset("pd-sharded", "pd",
           dataclasses.replace(_PAPER, graph_impl="sparse",
                               separation_chunk=64, separation_shards=4),
           "CSR PD with the repulsive chunk axis shard_mapped over up to 4 "
           "devices (clamped to the devices present; bit-identical)"),
    Preset("pd-state-sharded", "pd",
           dataclasses.replace(_PAPER, graph_impl="sparse",
                               first_round_cycles45=False, state_shards=4),
           "fully sharded solve: the whole SolverState (CSR included) "
           "edge-range-partitioned over up to 4 devices for the life of "
           "the solve (repro.core.sharded; 3-cycle separation; clamped to "
           "the devices present; bit-identical across shard counts)"),
):
    register_preset(_p)


# ---------------------------------------------------------------------------
# Compiled-executable cache (the registry the serving engine hangs off)
# ---------------------------------------------------------------------------

CACHE_MAXSIZE = 128     # default number of distinct (mode, config,
                        # backend, batched, shards, kind) executables kept
                        # live; LRU past that. Each entry is a jitted
                        # callable whose own shape-keyed XLA executables
                        # die with it on eviction.

KINDS = ("solve", "delta", "delta-warm", "delta-open")

_trace_count = [0]      # bumps once per executable *trace* (i.e. per XLA
                        # compilation triggered through this registry) —
                        # the instrumentation repro.serve uses to assert
                        # its ≤ buckets × routes compile budget.


def trace_count() -> int:
    """Number of solver traces (XLA compilations) that have run through the
    registry since process start / the last :func:`clear_cache`. A new
    (mode, config, backend) combination or a new input *shape* each add
    one; cache hits add none."""
    return _trace_count[0]


def _make_registry(maxsize: int):
    """Build the LRU executable registry. A factory (rather than a single
    decorated function) so :func:`set_cache_maxsize` can swap the bound in
    place — tests exercise eviction at maxsize=2 instead of compiling 129
    executables."""

    @lru_cache(maxsize=maxsize)
    def _compiled(mode: str, cfg: SolverConfig, backend: str, batched: bool,
                  batch_shards: int = 1, kind: str = "solve",
                  traced: bool = False):
        """One jitted callable per (mode, config, backend, batched,
        batch_shards, kind, traced) — the executable registry behind every
        public entrypoint and behind :class:`repro.serve.SolveEngine`'s
        dispatch.

        ``kind`` selects the traced program: "solve" takes an instance;
        "delta-open" takes an instance and returns (result, DeltaState);
        "delta"/"delta-warm" take (DeltaState, DeltaPatch) and return
        (result, DeltaState, PatchInfo). The trailing defaults keep solve
        cache keys identical to the pre-incremental registry.

        ``traced`` ("solve" kind only) compiles the telemetry-carrying
        variant: the callable returns ``(SolveResult, SolveTrace)`` (see
        :mod:`repro.obs.trace`). A separate registry entry by design —
        the traced executable carries extra while-loop leaves, and the
        untraced one must stay byte-for-byte the pre-trace program.

        ``batch_shards > 1`` (batched "solve" only) shard_maps the vmapped
        solve over the leading batch axis on the 1-D batch mesh from
        :func:`repro.core.dist.batch_mesh`: each device solves its
        contiguous slice of the batch independently (no collectives —
        instances are independent), so results are bit-identical to the
        unsharded batch.
        """
        if traced and kind != "solve":
            raise ValueError(f"trace=True applies to kind='solve' "
                             f"executables only (got kind={kind!r}); delta "
                             f"re-solves do not thread a SolveTrace yet")
        sweep = resolve_sweep(backend)
        intersect = resolve_intersect(backend)

        if kind == "solve":
            def run(inst: MulticutInstance):
                _trace_count[0] += 1        # executes at trace time only
                return solve_device(inst, mode=mode, cfg=cfg, sweep=sweep,
                                    intersect=intersect, trace=traced)
        elif kind == "delta-open":
            def run(inst: MulticutInstance):
                _trace_count[0] += 1
                return solve_cold_device(inst, mode, cfg, sweep=sweep,
                                         intersect=intersect)
        elif kind in ("delta", "delta-warm"):
            warm = kind == "delta-warm"

            def run(state: DeltaState, patch: DeltaPatch):
                _trace_count[0] += 1
                return solve_delta_device(state, patch, mode, cfg,
                                          sweep=sweep, intersect=intersect,
                                          warm=warm)
        else:
            raise ValueError(f"unknown executable kind {kind!r}; expected "
                             f"one of {KINDS}")

        if not batched:
            return jax.jit(run)
        if cfg.state_shards:
            raise ValueError(
                "state_shards and batched solves are mutually exclusive "
                "(one device mesh): a state-sharded solve already spans "
                "the devices a batch axis would shard over")
        fn = jax.vmap(run)
        if batch_shards > 1:
            if kind != "solve":
                raise ValueError("batch_shards applies to kind='solve' "
                                 "executables only (delta batches are "
                                 "vmapped, not sharded)")
            if cfg.separation_shards > 1:
                raise ValueError(
                    "batch_shards and SolverConfig.separation_shards are "
                    "mutually exclusive (one device axis): route large "
                    "instances to separation sharding OR shard the batch "
                    "axis")
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from repro.core.dist import batch_mesh
            fn = shard_map(fn, mesh=batch_mesh(batch_shards),
                           in_specs=P("batch"), out_specs=P("batch"),
                           check_vma=False)
        return jax.jit(fn)

    return _compiled


_compiled = _make_registry(CACHE_MAXSIZE)


def set_cache_maxsize(maxsize: int) -> None:
    """Swap the executable registry for a fresh one bounded at ``maxsize``
    and reset :func:`trace_count`. Every cached executable is dropped —
    this is a (re)configuration knob for tests and long-lived serving
    processes, not a per-request one."""
    global _compiled
    _compiled = _make_registry(int(maxsize))
    _trace_count[0] = 0


def compiled_solve(mode: str | None = None,
                   config: SolverConfig | None = None,
                   backend: str | None = None,
                   preset: str | Preset | None = None,
                   batched: bool = False, batch_shards: int = 1,
                   trace: bool = False):
    """Public accessor to the executable registry: the cached jitted
    callable :func:`solve` / :func:`solve_batch` would dispatch to. The
    serving engine uses this to warm up and dispatch per-bucket
    executables without re-deriving the routing each call.

    ``batch_shards`` is clamped to the devices present (a router asking
    for 4 still serves on a 1-device host), and the clamp happens *before*
    the cache key is formed so both spellings share one executable.
    ``trace=True`` returns the telemetry-carrying executable (its own
    registry entry; the callable returns ``(SolveResult, SolveTrace)``).
    """
    mode, config, backend = _normalize(mode, config, backend, preset)
    if batch_shards > 1 and not batched:
        raise ValueError("batch_shards applies to batched executables only")
    from repro.core.dist import resolve_batch_shards
    if trace:
        return _compiled(mode, config, backend, batched,
                         resolve_batch_shards(batch_shards), "solve", True)
    return _compiled(mode, config, backend, batched,
                     resolve_batch_shards(batch_shards))


def compiled_delta(mode: str | None = None,
                   config: SolverConfig | None = None,
                   backend: str | None = None,
                   preset: str | Preset | None = None,
                   warm: bool = False, batched: bool = False):
    """Cached delta executable: a jitted ``(DeltaState, DeltaPatch) ->
    (SolveResult, DeltaState, PatchInfo)`` callable (every leaf gains a
    leading batch axis when ``batched`` — the serving tier's sticky-session
    dispatch). Same registry as :func:`compiled_solve`."""
    mode, config, backend = _normalize(mode, config, backend, preset)
    if warm and mode == "d":
        raise ValueError("warm delta re-solve needs a primal solution to "
                         "lift; mode 'd' produces none")
    return _compiled(mode, config, backend, batched, 1,
                     "delta-warm" if warm else "delta")


def clear_cache() -> None:
    """Drop every cached executable (and with them their XLA compilations)
    and reset :func:`trace_count`. Mainly for tests and long-lived serving
    processes that change routing configuration wholesale."""
    _compiled.cache_clear()
    _trace_count[0] = 0


def cache_info():
    """``functools.lru_cache`` statistics of the executable registry
    (hits/misses/maxsize/currsize)."""
    return _compiled.cache_info()


def tree_ready(tree) -> bool:
    """Non-blocking readiness probe for a pytree of device arrays.

    Every registry executable dispatches asynchronously — the returned
    arrays are device futures, and the only host synchronisation happens
    when someone *reads* them. ``tree_ready`` answers "has the device
    finished computing this result?" without forcing that sync: True iff
    every leaf that exposes ``jax.Array.is_ready`` reports ready (host
    numpy leaves are trivially ready). This is the handle the serving
    engine's overlapped dispatch harvests on: dispatch N batches, keep
    admitting requests, and demux each result only once it polls ready.
    """
    for leaf in jax.tree.leaves(tree):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is not None and not is_ready():
            return False
    return True


def _normalize(mode, config, backend, preset, graph_impl=None):
    if preset is not None:
        p = get_preset(preset) if isinstance(preset, str) else preset
        mode = p.mode if mode is None else mode
        config = p.config if config is None else config
    mode = "pd" if mode is None else mode
    config = SolverConfig() if config is None else config
    if graph_impl is not None:
        if graph_impl not in GRAPH_IMPLS:
            raise ValueError(f"unknown graph_impl {graph_impl!r}; expected "
                             f"one of {GRAPH_IMPLS}")
        config = dataclasses.replace(config, graph_impl=graph_impl)
    backend = "reference" if backend is None else backend
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    return mode, config, backend


# ---------------------------------------------------------------------------
# Functional entrypoints
# ---------------------------------------------------------------------------

def solve(inst: MulticutInstance, mode: str | None = None,
          config: SolverConfig | None = None, backend: str | None = None,
          preset: str | Preset | None = None,
          graph_impl: str | None = None,
          tune_sparse_caps: bool = False, trace: bool = False):
    """Solve one multicut instance. The whole solve — separation, message
    passing, contraction, outer rounds — is a single device executable.
    ``graph_impl`` overrides the config's dense/sparse/auto data path.

    ``trace=True`` returns ``(SolveResult, SolveTrace)``: per-round lower
    bound / objective / conflicted-cycle count / edges contracted /
    MP improvement (plus per-shard balance on ``state_shards`` solves),
    captured inside the jitted round loop with ZERO additional host
    syncs — the trace arrays ride back with the result; digest them with
    :func:`repro.obs.summarize`. Labels/objective/LB stay bitwise
    identical to the untraced solve (pinned in tests/test_obs_trace.py);
    the traced executable is a separate registry entry, so flipping the
    flag never invalidates the untraced cache.

    ``tune_sparse_caps=True`` runs the serving engine's one-shot
    ``sparse_row_cap_short`` tuner before the executable lookup: a
    host-side pre-trace pass over the instance's attractive-degree
    histogram picks the p95 degree (clamped to ``[ROW_CAP_FLOOR,
    sparse_row_cap]``, same clamp as the per-bucket serve tuner) so
    ~95% of CSR rows take the narrow separation pass. Results are
    bit-identical for any cap (the degree buckets cover every row);
    only wall-clock changes. No-op for dense-resolved solves. Each
    distinct tuned cap compiles its own executable — reuse a
    :class:`~repro.serve.SolveEngine` for per-bucket caching instead of
    calling this on many differently-shaped instances."""
    mode, config, backend = _normalize(mode, config, backend, preset,
                                       graph_impl)
    if tune_sparse_caps:
        from repro.core.graph import (ROW_CAP_FLOOR, attractive_degree_p95,
                                      resolve_graph_impl)
        impl = resolve_graph_impl(config.graph_impl, inst.num_nodes,
                                  config.sparse_threshold)
        if impl == "sparse":
            cap = attractive_degree_p95(inst, ROW_CAP_FLOOR,
                                        config.sparse_row_cap)
            config = dataclasses.replace(config, sparse_row_cap_short=cap)
    if trace:
        return _compiled(mode, config, backend, False, 1, "solve",
                         True)(inst)
    return _compiled(mode, config, backend, False, 1)(inst)


def solve_batch(batch: MulticutInstance, mode: str | None = None,
                config: SolverConfig | None = None,
                backend: str | None = None,
                preset: str | Preset | None = None,
                graph_impl: str | None = None,
                batch_shards: int = 1) -> SolveResult:
    """Solve a stacked batch of same-shape instances with one vmapped
    executable. ``batch`` is a MulticutInstance whose every leaf carries a
    leading batch axis (see :func:`stack_instances`); the returned
    SolveResult is batched the same way (see :func:`unstack_results`).
    ``batch_shards > 1`` splits the batch axis over that many devices
    (clamped to the devices present; the batch size must divide evenly);
    results are bit-identical to the unsharded solve."""
    mode, config, backend = _normalize(mode, config, backend, preset,
                                       graph_impl)
    from repro.core.dist import resolve_batch_shards
    shards = resolve_batch_shards(batch_shards)
    B = batch.node_valid.shape[0]
    if B % shards:
        raise ValueError(
            f"batch size {B} is not divisible by the {shards} resolved "
            f"batch shard(s); pad the batch (see repro.serve.pad_batch) "
            f"or pick a shard count that divides it")
    return _compiled(mode, config, backend, True, shards)(batch)


def solve_with_state(inst: MulticutInstance, mode: str | None = None,
                     config: SolverConfig | None = None,
                     backend: str | None = None,
                     preset: str | Preset | None = None,
                     graph_impl: str | None = None,
                     ) -> tuple[SolveResult, DeltaState]:
    """Solve and open a delta session: like :func:`solve`, but also returns
    the :class:`DeltaState` (patched instance + live CSR + labels) that
    :func:`solve_delta` carries forward. The state's CSR feeds this very
    solve on the sparse path, so opening a session costs no extra sort."""
    mode, config, backend = _normalize(mode, config, backend, preset,
                                       graph_impl)
    return _compiled(mode, config, backend, False, 1, "delta-open")(inst)


def solve_delta(state: DeltaState, patch: DeltaPatch,
                mode: str | None = None,
                config: SolverConfig | None = None,
                backend: str | None = None,
                preset: str | Preset | None = None,
                graph_impl: str | None = None, warm: bool = False,
                ) -> tuple[SolveResult, DeltaState]:
    """One incremental update tick: apply ``patch`` to the carried
    ``state`` on device (CSR spliced, never rebuilt) and re-solve.
    Returns ``(result, new_state)``; thread the new state into the next
    tick.

    Exact mode (default) is bit-identical — objective, lower bound and
    labels — to a cold :func:`solve` of the patched instance. ``warm=True``
    lifts the previous solution instead: clusters untouched by the patch
    (no endpoint within ``config.delta_halo`` hops) stay contracted and
    round-0 separation is restricted to the patch frontier — much faster
    under small churn, at the price of dual tightness: the result's
    ``lower_bound`` is the *carried* bound — the last exact/cold tick's
    bound corrected by the patch slack ``Σ min(0, Δcost)`` — valid for the
    patched problem but looser than a fresh dual solve (the objective is
    still exact for the returned labels)."""
    mode, config, backend = _normalize(mode, config, backend, preset,
                                       graph_impl)
    if warm and mode == "d":
        raise ValueError("warm delta re-solve needs a primal solution to "
                         "lift; mode 'd' produces none")
    kind = "delta-warm" if warm else "delta"
    res, state2, _ = _compiled(mode, config, backend, False, 1,
                               kind)(state, patch)
    return res, state2


def stack_instances(instances: list[MulticutInstance]) -> MulticutInstance:
    """Stack same-shape instances along a new leading batch axis."""
    if not instances:
        raise ValueError("need at least one instance")
    shapes = {(i.num_nodes, i.num_edges) for i in instances}
    if len(shapes) > 1:
        raise ValueError(f"instances must share padded shapes; got {shapes} "
                         "(re-pad with make_instance(pad_nodes=, pad_edges=))")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *instances)


def unstack_results(batched: SolveResult) -> list[SolveResult]:
    """Split a batched SolveResult back into per-instance results."""
    B = batched.labels.shape[0]
    return [jax.tree.map(lambda x: x[b], batched) for b in range(B)]


# ---------------------------------------------------------------------------
# Facade
# ---------------------------------------------------------------------------

class Multicut:
    """Device-resident multicut solver bound to a (mode, config, backend).

    Thin, stateless facade over :func:`solve` / :func:`solve_batch`; the
    compiled executables live in the module-level cache, so constructing
    facades is free and two facades with equal settings share executables.
    """

    def __init__(self, mode: str = "pd",
                 config: SolverConfig | None = None,
                 backend: str = "reference",
                 graph_impl: str | None = None):
        self.mode, self.config, self.backend = _normalize(
            mode, config, backend, preset=None, graph_impl=graph_impl)

    @classmethod
    def from_preset(cls, name: str | Preset,
                    backend: str = "reference") -> "Multicut":
        p = get_preset(name) if isinstance(name, str) else name
        return cls(mode=p.mode, config=p.config, backend=backend)

    def replace(self, **kwargs) -> "Multicut":
        """New facade with some settings replaced; config fields (e.g.
        ``mp_iters=8``) are forwarded to ``dataclasses.replace`` on it."""
        cfg_fields = {f.name for f in dataclasses.fields(SolverConfig)}
        cfg_kw = {k: kwargs.pop(k) for k in list(kwargs) if k in cfg_fields}
        new = dict(mode=self.mode, backend=self.backend,
                   config=dataclasses.replace(self.config, **cfg_kw))
        new.update(kwargs)
        return Multicut(**new)

    def solve(self, inst: MulticutInstance, trace: bool = False):
        return solve(inst, mode=self.mode, config=self.config,
                     backend=self.backend, trace=trace)

    def solve_batch(self, batch: MulticutInstance) -> SolveResult:
        return solve_batch(batch, mode=self.mode, config=self.config,
                           backend=self.backend)

    def __repr__(self):
        return (f"Multicut(mode={self.mode!r}, backend={self.backend!r}, "
                f"config={self.config})")
