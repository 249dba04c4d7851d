"""Transfers: megabytes per study put from the host on the device, summed
over every program span's ``h2d_bytes``."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metric_program_common",
    pathlib.Path(__file__).with_name("_program.py"))
_program = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_program)


def read(run):
    return _program.megabytes(run, "h2d_bytes")
