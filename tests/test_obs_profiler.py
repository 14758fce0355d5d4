"""Program spans on the profiler's clock: under ``jax.profiler`` every span
a :class:`Tracer` opens is also a host annotation of the same name, and a
mine with tracing off opens none."""

import numpy as np
import pytest

from repro.obs import NULL_TRACER, Tracer, current_tracer, use_tracer


def _host_events(trace_dir) -> list:
    import glob
    import os

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(line.name, ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session: a traced tiny mine inside the annotation
    ``traced``, then an untraced one inside ``untraced``."""
    import jax

    from repro.core import mine
    from repro.core.mapreduce import MapReduceRuntime
    rng = np.random.default_rng(0)
    txns = [sorted(set(rng.integers(0, 10, rng.integers(2, 6)).tolist()))
            for _ in range(60)]
    rt = MapReduceRuntime(impl="vertical", autotune=False)
    mine(txns, n_items=10, min_sup=0.2, runtime=rt)  # compile before tracing
    tracer = Tracer()
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("traced"), use_tracer(tracer):
            mine(txns, n_items=10, min_sup=0.2, runtime=rt)
        with jax.profiler.TraceAnnotation("untraced"):
            assert current_tracer() is NULL_TRACER
            mine(txns, n_items=10, min_sup=0.2, runtime=rt)
    finally:
        jax.profiler.stop_trace()
    return tracer, _host_events(trace_dir)


def _inside(events, name):
    ((_, _, s, d),) = [e for e in events if e[1] == name]
    return [e for e in events if s <= e[2] and e[2] + e[3] <= s + d]


def test_every_span_is_a_profiler_annotation(profiled):
    tracer, events = profiled
    assert tracer.spans
    host = _inside(events, "traced")
    for name in {s.name for s in tracer.spans}:
        spans = sorted((s for s in tracer.spans if s.name == name),
                       key=lambda s: s.t0)
        marks = sorted((e for e in host if e[1] == name),
                       key=lambda e: e[2])
        assert len(marks) == len(spans), name
        for s, (_, _, _, dur_ns) in zip(spans, marks):
            assert abs(s.duration - dur_ns / 1e9) < 1e-3, name


def test_untraced_mine_opens_no_annotation(profiled):
    _, events = profiled
    host = _inside(events, "untraced")
    assert host                               # the annotation itself
    assert not [e for e in host if e[1].startswith("mine.")]
    assert NULL_TRACER.spans == [] and NULL_TRACER.events == []
