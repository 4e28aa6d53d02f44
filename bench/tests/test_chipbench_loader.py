"""The harness finds a configuration, a traffic mix and a per-layer
metric by the name BENCHMARK.json gives it: a file dropped into its
directory is used without editing any other."""
import json
import shutil

from bench import harness, trace_reduce
from bench.tests import tiny


def test_dropped_in_files_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs/tiny_grid.json").read_text())
    cfg.update(name="other_grid", rows=6, cols=6, nodes=36,
               edges_padded=60 + 12 + 12 + 9)
    (bench / "configs/other_grid.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs/tiny_grid.py",
                bench / "configs/other_grid.py")
    traffic = json.loads((bench / "traffic/solve_pd.json").read_text())
    traffic["instance_seeds"] = [7, 8]
    (bench / "traffic/solve_pd_two.json").write_text(json.dumps(traffic))
    (bench / "metrics/loop.device_ms.py").write_text(
        "def read(ctx):\n    return ctx.per_round_ms('loop')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "other_grid", "source": "test",
                            "file": "bench/configs/other_grid.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other_pd", "config": "other_grid",
                              "traffic": "solve_pd_two", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "loop.device_ms", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "solver round loop",
                              "moves": "solve_s", "workloads": ["other_pd"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell(root, "other_pd")
    assert cell.traffic["instance_seeds"] == [7, 8]
    assert cell.config["rows"] == 6
    inst = cell.generate(cell.config, 1)
    assert inst.num_nodes == 36 and inst.num_edges == 93
    assert [m["name"] for m in cell.per_layer] == ["loop.device_ms"]
    ctx = trace_reduce.MetricContext(
        phase_s={"loop": 0.5}, busy_s=1.0, window_s=2.0, rounds=4, work={},
        device_kind="TPU v5 lite")
    assert harness.metric_reader(root, "loop.device_ms")(ctx) == 125.0
    # the tiny cells keep their own metrics
    assert "loop.device_ms" not in [
        m["name"] for m in harness.load_cell(root, "tiny_pd").per_layer]


def test_readers_return_nothing_without_a_reading(tmp_path):
    root = tiny.make_root(tmp_path)
    ctx = trace_reduce.MetricContext(
        phase_s={"separation": 0.2, "message_passing": 0.1}, busy_s=0.4,
        window_s=0.5, rounds=4,
        work={"message_passing": (1e6, 8.19e6)}, device_kind="TPU v5 lite")
    read = {n: harness.metric_reader(root, n) for n in (
        "contraction.device_ms", "contraction_roofline",
        "message_passing_roofline", "device.idle_share",
        "round.device_ms", "separation.device_ms")}
    assert read["contraction.device_ms"](ctx) is None
    assert read["contraction_roofline"](ctx) is None
    # bytes bound: 8.19e6 B at 819 GB/s = 10 us of the phase's 100 ms
    assert abs(read["message_passing_roofline"](ctx) - 0.01) < 1e-9
    assert abs(read["device.idle_share"](ctx) - 20.0) < 1e-9
    assert read["round.device_ms"](ctx) == 100.0
    assert read["separation.device_ms"](ctx) == 50.0
