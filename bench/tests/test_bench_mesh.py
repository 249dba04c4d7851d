"""The four-chip cell off the chip: ``mesh.busy_imbalance`` on a built trace
of four devices, and a traced rehearsal of ``large-bwsweep-4chip`` on four
virtual CPU devices in a child process (the device count is fixed before
JAX starts, so this process's one device cannot serve)."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402
import trace_reduce as TR  # noqa: E402

CELL = "large-bwsweep-4chip"


def _ops(intervals):
    s, e = zip(*intervals)
    return TR.Ops(np.asarray(s, np.int64), np.asarray(e, np.int64),
                  np.zeros(len(s), np.int32), ["%fusion"])


def _run(busy, chips=4, window=(0, 100)):
    """A run whose device ``/device:TPU:i`` ran ``busy[i]``'s intervals."""
    tr = TR.Trace(ops={f"/device:TPU:{i}": _ops(iv)
                       for i, iv in enumerate(busy)}, spans=[])
    return harness.Run(chips=chips, studies=[], trace=tr, trace_window=window)


def _read(run):
    return harness.metric_reader("mesh.busy_imbalance", ROOT)(run)


def test_the_imbalance_is_the_busiest_chip_over_the_mean():
    # busy 40, 20 (overlaps merged), 30, 30 ns in [0, 100): mean 30
    busy = [[(0, 40)], [(10, 25), (20, 30)], [(50, 80)], [(0, 10), (80, 120)]]
    assert _read(_run(busy)) == pytest.approx(40 / 30)
    # in [0, 60): 40, 20, 10, 10 ns, mean 20
    assert _read(_run(busy, window=(0, 60))) == pytest.approx(40 / 20)
    even = [[(0, 50)], [(10, 60)], [(20, 70)], [(30, 80)]]
    assert _read(_run(even)) == 1.0


def test_the_imbalance_reads_nothing_without_a_mesh_or_a_trace():
    busy = [[(0, 40)], [(10, 30)], [(50, 80)], [(0, 10)]]
    assert _read(_run(busy, chips=1)) is None
    assert _read(_run(busy[:3])) is None            # a chip left no plane
    assert _read(_run([[(0, 0)]] * 4)) is None      # no chip was busy
    assert _read(_run(busy, window=(0, 0))) is None
    assert _read(harness.Run(chips=4, studies=[])) is None


_REHEARSAL = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
import harness
if len(jax.devices()) < 4:
    print(json.dumps({"devices": len(jax.devices())}))
    raise SystemExit(0)
out = harness.run_cell(sys.argv[3], 2**31 + 4099, 0.3, True, t_start=0.0,
                       platform=None, cache=False,
                       workload_kw=dict(num_kernels=2, windows_per_kernel=2,
                                        scale=0.004))
print(json.dumps({"devices": len(jax.devices()), "out": out}))
"""


def test_a_traced_four_chip_rehearsal_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT": "4",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c", _REHEARSAL, str(BENCH), str(ROOT / "src"), CELL],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    if rec["devices"] < 4:
        pytest.skip(f"the child saw {rec['devices']} CPU devices, not 4")
    out = rec["out"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1 and out["device"]["count"] == 4
    assert out["checks"]["max_rel_gap"]["value"] == 0.0
    # the CPU profiler writes no device plane: every device_trace metric,
    # the imbalance among them, reads nothing here and is left out
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, CELL)
    by_source = {m["name"]: m["source"]
                 for m in harness.cell_metrics(bench, cell, "per_layer")}
    assert "mesh.busy_imbalance" in by_source
    got = set(out["metrics"])
    assert got == {n for n, s in by_source.items() if s != "device_trace"}
    assert out["device"]["busy_s"] == 0.0
