"""Scan steps, LazyPIM: device milliseconds inside the LazyPIM dispatches
per 1000 real lane-windows."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metric_scan_common", pathlib.Path(__file__).with_name("_scan.py"))
_scan = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_scan)


def read(run):
    return _scan.ms_per_kwin(run, lambda m: m == "lazypim")
