"""The program's spans and byte counters (``repro.runtime.spans``): with no
profiler recording they open nothing, keep nothing and count nothing; while
one records they nest, count each distinct array once per span, are kept in
the process, and a study's cached calls open none."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Study, grid, workload
from repro.runtime import spans

TINY = dict(num_kernels=2, windows_per_kernel=2, scale=0.01)


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every span
    opened, its metadata and what it set on close."""

    enabled = True
    opened: list = []

    @staticmethod
    def is_enabled():
        return FakeAnnotation.enabled

    def __init__(self, name, **meta):
        self.name, self.meta, self.closed = name, dict(meta), False
        FakeAnnotation.opened.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.closed = True

    def set_metadata(self, **meta):
        self.meta.update(meta)


@pytest.fixture
def fake(monkeypatch):
    FakeAnnotation.enabled, FakeAnnotation.opened = True, []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation


def _names(fake):
    return [a.name for a in fake.opened]


def test_off_opens_no_annotation_and_counts_nothing(fake):
    fake.enabled = False
    before = spans.recorded()
    x = jnp.arange(8, dtype=jnp.int32)
    with spans.span("outer", study=1) as sp:
        host = spans.d2h(x)
        dev = spans.h2d(np.ones(4, np.float32))
        assert spans._stack() == []
    assert fake.opened == [] and sp._ann is None
    assert spans.recorded() == before
    np.testing.assert_array_equal(host, np.arange(8))
    assert isinstance(dev, jax.Array) and dev.dtype == jnp.float32


def test_the_real_profiler_is_off_by_default():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with spans.span("x"):
        assert spans._stack() == []


def test_spans_nest_and_count_each_distinct_array_once_per_span(fake):
    a = jnp.zeros((4, 8), jnp.int32)       # 128 bytes
    b = jnp.zeros((16,), jnp.bool_)        # 16 bytes
    with spans.span("outer", study=7):
        spans.d2h(a)
        with spans.span("inner"):
            spans.d2h(a)
            spans.d2h(a)                   # read again: moves nothing
            spans.d2h(b)
            spans.d2h(np.zeros(100))       # already on the host
            spans.h2d(np.zeros((3, 5), np.float32))
            spans.h2d(a)                   # already on the device
            spans.h2d(np.arange(4), jnp.int32)
        spans.h2d(1.5, jnp.float32)
    outer, inner = fake.opened
    assert (outer.name, inner.name) == ("repro:outer", "repro:inner")
    assert outer.closed and inner.closed and spans._stack() == []
    assert outer.meta == {"study": 7, "d2h_bytes": 128, "h2d_bytes": 4}
    assert inner.meta == {"d2h_bytes": 128 + 16, "h2d_bytes": 60 + 16}


def test_the_process_keeps_each_closed_span_with_its_counters(fake):
    n = len(spans.recorded())
    with spans.span("outer", study=3):
        with spans.span("inner"):
            spans.d2h(jnp.zeros((4, 8), jnp.int32))
    with spans.span("next"):
        pass
    inner, outer, nxt = spans.recorded()[n:]
    assert [p[2] for p in (inner, outer, nxt)] == [
        "repro:inner", "repro:outer", "repro:next"]
    assert outer[3] == {"study": 3, "d2h_bytes": 0, "h2d_bytes": 0}
    assert inner[3] == {"d2h_bytes": 128, "h2d_bytes": 0}
    assert outer[0] <= inner[0] <= inner[1] <= outer[1] <= nxt[0] <= nxt[1]


def test_a_span_closes_and_leaves_the_stack_on_an_error(fake):
    with pytest.raises(ValueError):
        with spans.span("broken"):
            raise ValueError("boom")
    assert fake.opened[0].closed and spans._stack() == []


def test_a_study_opens_each_span_once_and_none_on_a_cache_hit(fake):
    st = Study(workloads=[workload("htap128", **TINY)],
               hw=grid(offchip_bw_gbs=[16.0, 32.0]),
               mechanisms=("cpu", "lazypim"))
    st.traces()
    assert _names(fake) == ["repro:traces", "repro:synth", "repro:prepare",
                            "repro:pack"]
    assert fake.opened[0].meta["study"] == st.trace_id
    fake.opened.clear()
    st.traces()
    st.bucket_lanes()
    assert _names(fake) == ["repro:bucket_lanes", "repro:pad"]
    fake.opened.clear()
    st.traces(), st.bucket_lanes()
    assert fake.opened == []
    st.run(devices=1)
    assert _names(fake) == ["repro:run", "repro:stack", "repro:scan:cpu",
                            "repro:scan:lazypim", "repro:finalize"]
    run, scan = fake.opened[0], fake.opened[2]
    assert run.meta["study"] == st.trace_id and scan.meta["lanes"] == 2
    assert Study(workloads=["htap128"]).trace_id > st.trace_id
