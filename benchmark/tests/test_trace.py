"""The trace reduction on a small trace recorded here on the CPU.  The
CPU backend has no device plane, so its host plane stands in for one:
its XLA client threads carry the operations."""
import time

import pytest



def _load():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "trace.py")
    spec = importlib.util.spec_from_file_location("bench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("lp.columns"):
            time.sleep(0.2)  # host work, device idle
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    return d


def test_reduction_reads_busy_window_and_names_the_gap(recorded):
    from jax.profiler import ProfileData

    tr = _load()
    planes = list(ProfileData.from_file(tr.find_xplane(recorded)).planes)
    window = tr.trace_window_ns(planes)
    assert window is not None and window[1] - window[0] >= 0.2e9
    out = tr.reduce_planes(planes, window, device_prefix="/host:CPU",
                           ops_line="tf_XLAPjRtCpuClient",
                           modules_line="no such line")
    assert out is not None
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["window_s"] == pytest.approx((window[1] - window[0]) / 1e9)
    name, length = out["idle_gaps"][0]
    assert name == "lp.columns" and length >= 0.19
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])


def test_no_device_plane_gives_nothing(recorded):
    from jax.profiler import ProfileData

    tr = _load()
    planes = list(ProfileData.from_file(tr.find_xplane(recorded)).planes)
    assert tr.reduce_planes(planes, tr.trace_window_ns(planes)) is None


def test_union_merges_overlaps():
    tr = _load()
    total, merged = tr._union([(0, 10), (5, 20), (30, 40), (40, 41)])
    assert total == 31 and merged == [[0, 20], [30, 41]]
