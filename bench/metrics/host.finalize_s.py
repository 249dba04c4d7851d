"""Dispatch: seconds per study splitting lanes and finalizing results
(``repro:finalize``)."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metric_program_common",
    pathlib.Path(__file__).with_name("_program.py"))
_program = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_program)


def read(run):
    return _program.self_s(run, "repro:finalize")
