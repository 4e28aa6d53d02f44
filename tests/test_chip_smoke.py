"""``chip_smoke.py``'s logic on the CPU at a tiny size.

The phases are the script's own functions, loaded by file path (the repo
root is not on the tests' ``sys.path``), run with the kernels interpreted:
the solve checks, the serve bit-identity checks, and the four-chip
comparison on four virtual devices in a subprocess. The device gate is
tested to refuse the CPU, in-process and as the script a user would run.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
SRC = ROOT / "src"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load()


def _env(devices: int | None = None) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    if devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def test_device_gate_refuses_cpu():
    with pytest.raises(SystemExit) as e:
        smoke.device_gate()
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)


@pytest.mark.parametrize("alone", [False, True])
def test_script_exits_nonzero_without_chip_or_repo(tmp_path, alone):
    """On the CPU the script stops at the device gate; copied into a
    directory with nothing else of the repo it stops before importing
    anything. Neither prints the result line."""
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         capture_output=True, text=True, env=_env(),
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert ("no repro package" if alone else "no TPU") in out.stderr


def test_solve_phase_tiny(monkeypatch):
    # off the chip the kernels are interpreted, so record the kernel check
    # instead of running it (it has its own test below)
    checked = []
    monkeypatch.setattr(smoke, "check_kernels",
                        lambda label, text: checked.append(label))
    recs = smoke.solve_phase(big_side=16, small_side=12)
    assert checked == ["grid16/pd/pallas"]
    assert [(r["mode"], r["backend"]) for r in recs] == [
        ("pd", "reference"), ("pd", "pallas"), ("pd", "reference"),
        ("p", "reference"), ("pd+", "reference"), ("d", "reference")]
    for r in recs:
        assert r["rounds"] >= 1 and r["compile_s"] > 0 and r["warm_s"] > 0
    assert recs[0]["lower_bound"] <= recs[0]["objective"]


def test_serve_runs_alongside_the_solve_compiles(monkeypatch, capsys):
    """The full run's order: serve in a thread while the solve
    executables compile and then run. The serve phase's compile count and
    the solves' no-retrace check must both still hold."""
    from repro import api
    api.clear_cache()       # count this test's compiles, not earlier ones
    monkeypatch.setattr(smoke, "check_kernels", lambda label, text: None)
    served = []
    recs = smoke.solve_phase(
        big_side=16, small_side=12,
        alongside=lambda: served.append(
            smoke.serve_phase(tile_sides=(8, 12), tiles_per_side=2)))
    assert len(recs) == 6 and len(served) == 1
    assert served[0]["stream_compiles"] >= 2
    out = capsys.readouterr().out
    assert "compile: 6 executables" in out
    assert out.count("] compiled grid") == 6


def test_failed_serve_phase_fails_the_solve_phase(monkeypatch):
    monkeypatch.setattr(smoke, "check_kernels", lambda label, text: None)

    def serve():
        raise smoke.SmokeFailure("serve: tile 0 differs")

    with pytest.raises(smoke.SmokeFailure, match="tile 0 differs"):
        smoke.solve_phase(big_side=9, small_side=7, alongside=serve)


def test_kernel_check_refuses_interpreted_kernels():
    """Off the chip the pallas executable holds the Pallas interpreter,
    not Mosaic kernels, and the smoke's kernel check must say so."""
    from repro import api
    from repro.core.graph import grid_instance
    from repro.core.solver import SolverConfig

    inst = grid_instance(16, 16, seed=0)
    text = api.compiled_solve("pd", SolverConfig(), "pallas").lower(
        inst).compile().as_text()
    with pytest.raises(smoke.SmokeFailure, match="interpret mode"):
        smoke.check_kernels("grid16/pd/pallas", text)


def test_solve_check_catches_wrong_objective(monkeypatch):
    """A device objective that disagrees with the host recomputation
    fails the run."""
    from repro.core import baselines
    from repro.core.graph import grid_instance

    monkeypatch.setattr(baselines, "objective",
                        lambda inst, labels: 1e9)
    inst = grid_instance(12, 12, seed=0)
    compiled = smoke.compile_all(
        {"grid12/p": smoke.lower_solve(inst, "p")})["grid12/p"]
    with pytest.raises(smoke.SmokeFailure, match="host"):
        smoke.run_solve("grid12/p", inst, "p", "reference", compiled)


def test_solve_check_refuses_a_compiling_call():
    """The timed call must reuse the executable compiled for it: handed
    another mode's, ``api.solve`` compiles, and the run fails."""
    from repro.core.graph import grid_instance

    inst = grid_instance(10, 10, seed=0)
    compiled = smoke.compile_all(
        {"grid10/p": smoke.lower_solve(inst, "p")})["grid10/p"]
    with pytest.raises(smoke.SmokeFailure, match="compiled again"):
        smoke.run_solve("grid10/pd", inst, "pd", "reference", compiled)


def test_serve_phase_tiny():
    from repro import api
    api.clear_cache()       # count this test's compiles, not earlier ones
    rec = smoke.serve_phase(tile_sides=(8, 12), tiles_per_side=2)
    assert rec["tiles"] == 4
    assert rec["stream_compiles"] >= 2          # one per bucket at least
    assert len(rec["delta_tick_s"]) == 2


def test_four_chip_phase_refuses_fewer_devices():
    with pytest.raises(smoke.SmokeFailure, match="devices"):
        smoke.four_chip_phase(side=12)


def test_four_chip_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(SCRIPT)!r})
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        m.four_chip_phase(side=12)
        """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=_env(devices=4), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("four-chip ")][-1]
    rec = json.loads(line[len("four-chip "):])
    assert rec["bitwise_equal"] == {"labels": True, "objective": True,
                                    "lower_bound": True}
    assert len(rec["state_shards=4"]["peak_bytes_in_use"]) == 4
