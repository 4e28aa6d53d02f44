"""A checkout root with the benchmark's own files and two tiny cells of a
12 x 12 grid, for driving runs on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
SIDE = 12
EDGES = 537      # 4-connectivity 264, offsets (0,4) 96, (4,0) 96, (3,3) 81
PADDED = 1024    # 487 free slots for separation's chords
# The tiny cells' limits. On a 12 x 12 grid the dual separates cycles at
# every negative edge, and lifts the bound 4-8 % (CPU); over every other
# edge it reads 50 % or more.
TINY_LIMITS = {"objective_err": 1e-5, "bound_excess": 0.0,
               "bound_deficit": -0.01, "bound_lift": 0.3,
               "compiles_in_window": 0}


def tiny_config() -> dict:
    cfg = json.loads(
        (REPO / "bench/configs/cityscapes_grid256.json").read_text())
    cfg.update(name="tiny_grid", rows=SIDE, cols=SIDE,
               nodes=SIDE * SIDE, edges_padded=PADDED)
    return cfg


def make_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout: BENCHMARK.json naming the cells
    ``tiny_pd`` and ``tiny_d`` with their limits, the traffic and metric
    files copied, and the ``tiny_grid`` configuration with the grid
    generator."""
    bench = tmp / "bench"
    for sub in ("traffic", "metrics"):
        shutil.copytree(REPO / "bench" / sub, bench / sub)
    (bench / "limits").mkdir()
    for cell in ("tiny_pd", "tiny_d"):
        (bench / "limits" / f"{cell}.json").write_text(
            json.dumps(TINY_LIMITS))
    (bench / "configs").mkdir(parents=True)
    (bench / "configs/tiny_grid.json").write_text(json.dumps(tiny_config()))
    shutil.copy(REPO / "bench/configs/cityscapes_grid256.py",
                bench / "configs/tiny_grid.py")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny_grid",
                        "source": "test", "reduced": [], "why": "test",
                        "file": "bench/configs/tiny_grid.json"}]
    spec["workloads"] = [
        {"name": "tiny_pd", "config": "tiny_grid", "traffic": "solve_pd",
         "chips": 1, "why": "test"},
        {"name": "tiny_d", "config": "tiny_grid", "traffic": "solve_d",
         "chips": 1, "why": "test"}]
    rename = {"grid256_pd": "tiny_pd", "grid256_d": "tiny_d"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]
                              if w in rename]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
