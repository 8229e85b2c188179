"""The trace reduction: on hand-made intervals, and on a trace recorded on
the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace as T


def ops(*spec):
    return [T.Op(n, s, e) for n, s, e in spec]


def test_union_measure_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.measure([(0, 2), (1, 3), (10, 11)]) == 4
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                      (7, 10)]
    assert T.subtract([(0, 10)], [(0, 10)]) == []


def test_busy_idle_gaps_and_labels():
    dev = ops(("fusion.1", 10, 30), ("fusion.2", 20, 40), ("fusion.3", 60,
                                                         70))
    window = (0, 100)
    assert T.busy_ns(dev, window) == 40
    assert T.gaps(dev, window) == [(0, 10), (40, 60), (70, 100)]
    spans = ops(("bench.build", 0, 12), ("bench.dispatch", 40, 58),
                ("bench.wait_and_record", 70, 100))
    got = T.labelled_gaps([dev], spans, window, n=2)
    assert got == [["bench.wait_and_record@dev0", 30e-9],
                   ["bench.dispatch@dev0", 20e-9]]


def test_kernel_time_by_name():
    dev = ops(("adahessian_update_batched", 0, 5),
              ("adahessian_update_batched.1", 10, 13),
              ("elastic_update_batched", 20, 24), ("fusion", 30, 90))
    assert T.kernel_time(dev, "adahessian_update_batched", (0, 100)) == (8, 2)
    assert T.kernel_time(dev, "elastic_update_batched", (0, 100)) == (4, 1)
    assert T.kernel_time(dev, "elastic_update_batched", (0, 20)) == (0, 0)
    # per round, in ms, averaged over the devices that ran the kernel only
    other = ops(("fusion", 0, 50))
    assert T.kernel_ms_per_round([dev, other], "adahessian_update_batched",
                                 (0, 100), 2) == 4e-6
    assert T.kernel_ms_per_round([other], "elastic_update_batched",
                                 (0, 100), 2) is None


def test_exposed_collective():
    dev = ops(("all-gather-start.1", 0, 50), ("fusion.1", 10, 20),
              ("all-gather-done.1", 50, 60), ("fusion.2", 55, 70))
    # collective 0..60, compute 10..20 and 55..70: exposed 0..10, 20..55
    assert T.exposed_collective_ns(dev, (0, 100)) == 45
    assert T.exposed_collective_ns(ops(("fusion", 0, 9)), (0, 10)) == 0


def test_top_ops_average_over_devices():
    devs = [ops(("a", 0, 10), ("b", 10, 12)), ops(("a", 0, 30))]
    assert T.top_ops(devs, (0, 100), n=1) == [["a", 20e-9]]


def test_recorded_cpu_trace(tmp_path):
    """A jitted all-gather over four CPU devices, recorded with the
    benchmark's host spans: the reduction finds the spans, the operations,
    their time by name and the exposed collective time."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs four (virtual) devices")
    mesh = Mesh(np.array(devs[:4]), ("x",))
    f = jax.jit(jax.shard_map(
        lambda a: jax.lax.all_gather(a @ a.T, "x", tiled=True).sum(0),
        mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False))
    x = jnp.ones((4 * 64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            y = f(x)
        with jax.profiler.TraceAnnotation("bench.wait_and_record"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    lo, hi = tr.window
    assert len(tr.spans) == 6 and hi > lo
    assert tr.devices and tr.devices[0]
    dev = tr.devices[0]
    busy = T.busy_ns(dev, tr.window)
    assert 0 < busy <= hi - lo
    names = {o.name for o in dev}
    name = next(n for n in names if n.startswith("dot"))
    ns, calls = T.kernel_time(dev, name, tr.window)
    assert calls >= 1
    assert ns == sum(o.end - o.start for o in dev
                     if o.name.startswith(name) and lo <= o.start < hi)
    assert any(T.is_collective(n) for n in names)
    coll = T.measure(T.clip(((o.start, o.end) for o in dev
                             if T.is_collective(o.name)), lo, hi))
    assert 0 <= T.exposed_collective_ns(dev, tr.window) <= coll
