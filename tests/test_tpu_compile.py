"""The chip's compiler accepts the main path at real size, with no chip.

Each of the six batched mechanism scans (``engine._sweep_fn``) compiles
for one described TPU v5e chip at the 1 Mi-line bucket with 8 lanes (the
``chip_smoke.py`` large phase), and the word-level Bloom kernels compile
with ``interpret=False`` at the default spec and at ``sig_bits=8192``.
Nothing runs: a compile that passes here is not a chip run.  It catches
what interpret mode and the CPU backend cannot: tiling refusals, kernels
that use more fast memory than a core has, programs that overflow HBM.

The topology is described only inside the module fixture (one process at
a time may load the TPU library; describing it while a module is imported
would break collection under several test workers), and every test skips
where it cannot be described.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.coherence import LazyPIMConfig
from repro.core.signatures import SignatureSpec, default_spec
from repro.kernels.bloom import bloom as K
from repro.serve.warm import dummy_stacked
from repro.sim import engine

HBM_BYTES = 16 * 2**30  # TPU v5e, per chip
# htap128-large's bucket (benchmarks/bench_engine.py LARGE_WORKLOAD_KW).
LARGE_BUCKET = dict(num_lines=1 << 20, num_windows=384, num_kernels=24,
                    pim_read_slots=256, pim_write_slots=256,
                    cpu_read_slots=64, cpu_write_slots=64)
LANES = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def large_args(one_chip):
    lazy = LazyPIMConfig()
    entry = {**LARGE_BUCKET, "lanes": LANES, "devices": 1,
             "spec": dataclasses.asdict(default_spec()),
             "lazy_static": {f: getattr(lazy, f)
                             for f in engine._LAZY_STATIC_FIELDS}}
    return _on(one_chip, jax.eval_shape(functools.partial(dummy_stacked,
                                                          entry)))


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    print(mem)
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem


@pytest.mark.parametrize("mechanism", engine.MECHANISMS)
def test_sweep_scan_compiles_for_one_chip(mechanism, large_args):
    stt, shw, scfg = large_args
    args = (stt, shw, scfg) if mechanism == "lazypim" else (stt, shw)
    _fits(engine._sweep_fn(mechanism).lower(*args).compile())


_N, _G, _B = 4096, 4, 64  # addresses, group signatures, intersect pairs


def _kernel_case(name: str, spec: SignatureSpec):
    u32 = jnp.uint32
    sig = jax.ShapeDtypeStruct((spec.num_words,), u32)
    addrs = jax.ShapeDtypeStruct((_N,), u32)
    if name == "insert":
        return K.bloom_insert_pallas, (sig, addrs)
    if name == "query":
        return K.bloom_query_pallas, (sig, addrs)
    if name == "detect_conflicts":
        sigs = jax.ShapeDtypeStruct((_G, spec.num_words), u32)
        return K.bloom_detect_conflicts_pallas, (sigs, addrs)
    pair = jax.ShapeDtypeStruct((_B, spec.num_words), u32)
    return K.bloom_intersect_pallas, (pair, pair)


@pytest.mark.parametrize("sig_bits", [2048, 8192])
@pytest.mark.parametrize("name", ["insert", "query", "detect_conflicts",
                                  "intersect"])
def test_bloom_kernel_compiles_for_one_chip(name, sig_bits, one_chip):
    spec = dataclasses.replace(default_spec(), sig_bits=sig_bits)
    fn, args = _kernel_case(name, spec)
    step = jax.jit(functools.partial(fn, spec, interpret=False))
    compiled = step.lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
