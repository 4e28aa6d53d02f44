import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (
    PEAKS, collective_bytes, dominant_term, hardware, roofline_fraction,
    roofline_terms, step_time_estimate,
)

HW = hardware("TPU v5 lite")

HLO_SAMPLE = """
HloModule jit_step
%x = f32[128,256]{1,0} all-gather(%p0), replica_groups=[...]
%y = bf16[64]{0} all-reduce(%p1), to_apply=%add
%z = (f32[32,32]{1,0}) reduce-scatter(%p2)
%w = f32[16,16]{1,0} collective-permute(%p3)
%notacoll = f32[999,999]{1,0} add(%a, %b)
"""


def test_collective_bytes_parsing():
    out = collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 128 * 256 * 4
    assert out["all-reduce"] == 64 * 2 * 2          # bf16, 2x ring factor
    assert out["reduce-scatter"] == 32 * 32 * 4
    assert out["collective-permute"] == 16 * 16 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_collective_bytes_ignores_elementwise():
    out = collective_bytes("%a = f32[10]{0} add(%x, %y)\n")
    assert out["total"] == 0


def test_roofline_terms_units():
    terms = roofline_terms(flops=HW.peak_flops, bytes_accessed=HW.hbm_bw,
                           coll_bytes=HW.ici_bw, hw=HW)
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(1.0)
    assert terms["collective_s"] == pytest.approx(1.0)


def test_dominant_term():
    assert dominant_term({"compute_s": 3, "memory_s": 1,
                          "collective_s": 2}) == "compute"
    assert dominant_term({"compute_s": 0, "memory_s": 1,
                          "collective_s": 2}) == "collective"


def test_step_time_overlap_vs_serial():
    t = {"compute_s": 3.0, "memory_s": 1.0, "collective_s": 2.0}
    assert step_time_estimate(t, overlap=True) == 3.0
    assert step_time_estimate(t, overlap=False) == 6.0


def test_roofline_fraction_bounds():
    terms = {"compute_s": 1.0, "memory_s": 0.5, "collective_s": 0.1}
    # if all HLO flops were useful, fraction == compute_s / step_time == 1
    frac = roofline_fraction(HW.peak_flops * 1.0, terms, HW)
    assert frac == pytest.approx(1.0)
    # half-useful flops -> 0.5
    frac = roofline_fraction(HW.peak_flops * 0.5, terms, HW)
    assert frac == pytest.approx(0.5)


def test_hardware_table_keyed_by_device_kind():
    """Peaks come from the table, keyed by jax's device_kind; a device
    missing from it (the CPU included) is an error, never a default."""
    assert HW is PEAKS["TPU v5 lite"]
    assert HW.peak_flops == 197e12 and HW.hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        hardware(jax.devices("cpu")[0].device_kind)


def test_roofline_fields_need_chip_peaks():
    """Without a chip's peaks a phase record's roofline fields are None
    ("not measured"), not one chip's shares of another device's wall."""
    from repro.roofline.solver import _roofline
    assert _roofline(1e9, 1e9, 0.0, None) == {
        "terms": None, "dominant": None, "roofline_s": None}
    rec = _roofline(1e9, 1e9, 0.0, HW)
    assert rec["dominant"] == "memory"
    assert rec["roofline_s"] == pytest.approx(1e9 / HW.hbm_bw)


def test_collective_bytes_on_real_compile():
    """Compile a psum on 1 device — no cross-device collective should be
    charged (XLA elides trivial groups) or, if present, counted finitely."""
    f = jax.jit(lambda x: x * 2 + 1)
    hlo = f.lower(jnp.ones((8, 8))).compile().as_text()
    out = collective_bytes(hlo)
    assert out["total"] == 0


def test_loop_corrected_linear():
    from repro.roofline.solver import loop_corrected
    # setup 10, per-iter 5: depth-1 = 15, depth-2 = 20, depth-8 = 50
    assert loop_corrected(15.0, 20.0, 8) == pytest.approx(50.0)
    assert loop_corrected(15.0, 20.0, 1) == pytest.approx(15.0)


@pytest.mark.parametrize("impl", ["dense", "sparse"])
def test_profile_solve_round(impl):
    """One round profiled end to end on both data paths: every phase gets
    measured flops/bytes/wall, MP carries the loop-trip correction, and
    the round totals are the phase sums."""
    from repro.core.graph import random_instance
    from repro.core.solver import SolverConfig
    from repro.roofline.solver import PHASES, profile_solve_round

    inst = random_instance(40, 0.2, seed=0, pad_edges=256, pad_nodes=64)
    cfg = SolverConfig(max_neg=64, max_tri_per_edge=4, nbr_k=4, mp_iters=3,
                       graph_impl=impl)
    prof = profile_solve_round(inst, cfg)
    assert prof["impl"] == impl
    assert set(prof["phases"]) == set(PHASES)
    assert prof["hw"] is None and prof["round_roofline_s"] is None
    for rec in prof["phases"].values():
        assert rec["wall_s"] > 0
        assert rec["flops"] >= 0 and rec["bytes_accessed"] > 0
        # CPU walls: no chip peaks, so no roofline share
        assert rec["dominant"] is None and rec["roofline_s"] is None
    loop = prof["phases"]["message_passing"]["loop"]
    assert loop["iters"] == cfg.mp_iters
    # depth-2 does strictly more work than depth-1
    assert loop["flops_depth2"] > loop["flops_depth1"]
    assert prof["round_wall_s"] == pytest.approx(
        sum(p["wall_s"] for p in prof["phases"].values()))


def _sharded_cfg(shards):
    from repro.core.solver import SolverConfig
    return SolverConfig(max_neg=64, max_tri_per_edge=4, nbr_k=4, mp_iters=3,
                        graph_impl="sparse", first_round_cycles45=False,
                        state_shards=shards)


def _check_sharded_profile(prof, shards):
    """The SPMD accounting identity: every shard runs the identical
    per-device program, so job totals are EXACTLY per_device x shards."""
    from repro.roofline.solver import PHASES
    assert prof["impl"] == "sparse"
    assert prof["state_shards"] == shards
    assert set(prof["phases"]) == set(PHASES)
    for name, rec in prof["phases"].items():
        assert rec["wall_s"] > 0, name
        assert rec["flops"] == rec["flops_per_device"] * shards, name
        assert rec["bytes_accessed"] == \
            rec["bytes_accessed_per_device"] * shards, name
        assert rec["collective_bytes"] == \
            rec["collective_bytes_per_device"] * shards, name
        assert rec["dominant"] is None, name        # CPU: not measured
    loop = prof["phases"]["message_passing"]["loop"]
    assert loop["flops_depth2"] > loop["flops_depth1"]
    assert prof["round_wall_s"] == pytest.approx(
        sum(p["wall_s"] for p in prof["phases"].values()))


def test_profile_solve_round_sharded_single_device():
    """state_shards=1 dispatches to the sharded profiler (shard_map over
    one device): same phases, and the per-device identity is trivial."""
    from repro.core.graph import random_instance
    from repro.roofline.solver import profile_solve_round

    inst = random_instance(40, 0.2, seed=0, pad_edges=256, pad_nodes=64)
    prof = profile_solve_round(inst, _sharded_cfg(1))
    _check_sharded_profile(prof, 1)


def test_profile_solve_round_sharded_4_devices():
    """On 4 virtual devices: per-phase job flops/bytes are exactly the
    per-device numbers x 4 (identical SPMD programs), and the halo
    exchanges show up as nonzero collective bytes."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import json
        import jax
        from repro.core.graph import random_instance
        from repro.core.solver import SolverConfig
        from repro.roofline.solver import profile_solve_round

        assert jax.device_count() == 4
        inst = random_instance(40, 0.2, seed=0, pad_edges=256, pad_nodes=64)
        cfg = SolverConfig(max_neg=64, max_tri_per_edge=4, nbr_k=4,
                           mp_iters=3, graph_impl="sparse",
                           first_round_cycles45=False, state_shards=4)
        print(json.dumps(profile_solve_round(inst, cfg)))
        """)], capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    prof = json.loads(out.stdout.splitlines()[-1])
    _check_sharded_profile(prof, 4)
    assert prof["phases"]["separation"]["collective_bytes"] > 0
