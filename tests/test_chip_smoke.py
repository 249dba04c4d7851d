"""``chip_smoke.py``'s phases at tiny sizes on CPU, pass and fail logic
intact, and its refusal to run anywhere but on a TPU."""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.api import Study, grid, workload
from repro.sim import engine

ROOT = pathlib.Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

_SMALL = dict(num_kernels=3, windows_per_kernel=2)
PAGERANK = workload("pagerank", "arxiv", scale=0.4, **_SMALL)
HTAP = workload("htap128", scale=0.004, **_SMALL)
MECHS = ("cpu", "fg", "lazypim")


def test_main_refuses_cpu(capsys):
    for argv in ([], ["--chips", "4"]):
        assert chip_smoke.main(argv) == 1
        out, err = capsys.readouterr()
        assert "no TPU" in err
        assert '"ok"' not in out


def test_phase_device_reports_the_platform(capsys):
    rec = chip_smoke.phase_device(platform="cpu")
    assert rec["platform"] == "cpu" and rec["count"] >= 1
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == rec
    with pytest.raises(chip_smoke.SmokeFailure, match="need 99"):
        chip_smoke.phase_device(platform="cpu", min_count=99)


def test_phase_golden_passes_and_names_a_drifted_field(tmp_path):
    rs = Study(workloads=[PAGERANK], mechanisms=MECHS).run(
        engine="sequential")
    golden = {p.workload: s for p, s in zip(rs.points, rs.normalized())}
    path = tmp_path / "golden.json"
    path.write_text(json.dumps(golden))
    rec = chip_smoke.phase_golden([PAGERANK], path, mechanisms=MECHS)
    assert rec["workloads"] == list(golden)

    (name,) = golden
    golden[name]["lazypim"]["speedup"] *= 1 + 2e-6   # past RATIO_RTOL
    golden[name]["fg"]["time_ns"] *= 1 + 5e-5        # inside RAW_RTOL
    path.write_text(json.dumps(golden))
    with pytest.raises(chip_smoke.SmokeFailure) as e:
        chip_smoke.phase_golden([PAGERANK], path, mechanisms=MECHS)
    assert f"{name}/lazypim/speedup" in str(e.value)
    assert "time_ns" not in str(e.value)


def test_golden_mismatches_flags_missing_workloads_and_mechanisms():
    assert chip_smoke.golden_mismatches({"a": {}}, {"b": {}})
    got = chip_smoke.golden_mismatches(
        {"a": {"cpu": {"speedup": 1.0}}},
        {"a": {"cpu": {"speedup": 1.0}, "fg": {"speedup": 1.0}}})
    assert got and "mechanisms" in got[0]


def test_phase_fleet_checks_plan_against_measured_compiles():
    engine._sweep_fn.cache_clear()  # cold jit caches, as in a fresh process
    study = Study(workloads=[PAGERANK, HTAP], mechanisms=MECHS)
    rec = chip_smoke.phase_fleet(study)
    assert rec["compiles"] == {m: rec["buckets"] for m in MECHS}
    # Warm caches: the planner's cold prediction no longer holds.
    with pytest.raises(chip_smoke.SmokeFailure, match="planned compiles"):
        chip_smoke.phase_fleet(
            Study(workloads=[PAGERANK, HTAP], mechanisms=MECHS))


def test_phase_large_lane0_matches_sequential():
    study = Study(workloads=[HTAP], hw=grid(offchip_bw_gbs=[16.0, 32.0]),
                  mechanisms=MECHS)
    rec = chip_smoke.phase_large(study, devices=1)
    assert rec["lanes"] == 2 and rec["devices"] == 1


def test_diff_fields_names_every_differing_field():
    study = Study(workloads=[HTAP], mechanisms=("cpu",))
    a, b = study.run(), study.run()
    assert chip_smoke._diff_fields(a, b) == []
    r = b.points[0].results["cpu"]
    b.points[0].results["cpu"] = type(r)(**{**vars(r), "time_ns": 0.0})
    (diff,) = chip_smoke._diff_fields(a, b)
    assert "/cpu/time_ns" in diff


def test_phase_served_all_batch_and_repeat_compiles_nothing():
    rec = chip_smoke.phase_served(chip_smoke.SERVED_SPECS)
    assert rec["requests"] == len(chip_smoke.SERVED_SPECS) + 1
    assert rec["repeat_compiles"] == 0


def test_phase_served_refuses_a_rejected_request():
    with pytest.raises(chip_smoke.SmokeFailure, match="responses"):
        chip_smoke.phase_served(
            [chip_smoke.SERVED_SPECS[0], {"workloads": ["no-such-app"]}])


_MESH_SCRIPT = r"""
import json
import chip_smoke
from repro.api import Study, grid, workload
study = Study(workloads=[workload("htap128", scale=0.004, num_kernels=3,
                                  windows_per_kernel=2)],
              hw=grid(offchip_bw_gbs=[16.0, 32.0, 48.0, 64.0]),
              mechanisms=("cpu", "lazypim"))
chip_smoke.phase_mesh(study, devices=4)
"""


def test_phase_mesh_on_four_virtual_devices():
    env = {**os.environ, "XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT": "4",
           "PYTHONPATH": os.pathsep.join(
               filter(None, [str(ROOT / "src"), str(ROOT),
                             os.environ.get("PYTHONPATH")]))}
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("input_shards", "shards"):
        devices = {s["device"] for s in rec["placement"][key]}
        assert len(devices) == 4, key
        assert all(s["lanes"] == 1 for s in rec["placement"][key]), key
