"""The trace reduction on a synthetic event list, and on a trace recorded
on the CPU (host annotations only)."""
from __future__ import annotations

import pytest

from chipbench import trace as tr
from chipbench.trace import Event


def host(window=(0, 100), stages=()):
    return ([Event(tr.WINDOW, *window)]
            + [Event(tr.STAGE_PREFIX + n, s, e) for n, s, e in stages])


def test_union_merges_and_clips():
    assert tr.union([(5, 10), (0, 3), (2, 4), (9, 12), (20, 30)], 1, 25) == [
        [1, 4], [5, 12], [20, 25]]


def test_busy_idle_and_op_texts():
    # one device: ops at [10, 30) and [20, 40) overlap -> busy 30;
    # [90, 120) is clipped to the window's end at 100 -> 10 more.  An op
    # that only reads a kernel's output is not the kernel.
    dev = [Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 30),
           Event("%seg_mean_kernel.1 = (f32[62,32]{1,0}) custom-call("
                 "f32[64,32]{1,0} %f)", 20, 40),
           Event("%pairwise_dist_kernel.3 = f32[512,8]{1,0} custom-call("
                 "f32[512,2048]{1,0} %x)", 90, 120),
           Event("%slice.2 = f32[280,8]{1,0} slice(f32[512,8]{1,0} "
                 "%pairwise_dist_kernel.3)", 35, 38)]
    stages = [("scan", 0, 50), ("select", 50, 100)]
    red = tr.reduce([dev], host(stages=stages))
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)
    texts = red["op_texts"]
    assert texts[dev[1].name] == (pytest.approx(20e-9), 1)
    assert texts[dev[2].name] == (pytest.approx(10e-9), 1)
    assert tr.instruction(dev[3].name) == "slice.2"
    # gaps: [0, 10) in scan, [40, 90) centred at 65 in select
    assert red["idle_gaps"] == [["select", pytest.approx(50e-9)],
                                ["scan", pytest.approx(10e-9)]]
    assert red["device_ops"][0] == ["%fusion.1 = f32[8]", pytest.approx(20e-9)]


def test_busy_is_averaged_over_devices():
    a = [Event("op", 0, 50)]
    b = [Event("op", 0, 100)]
    red = tr.reduce([a, b], host())
    assert red["busy_s"] == pytest.approx(75e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([[]], [])


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation(tr.STAGE_PREFIX + "scan"):
            jax.block_until_ready(jnp.ones(8) + 1)
    jax.profiler.stop_trace()
    devices, hosts = tr.read(str(tmp_path))
    assert devices == []          # the CPU backend has no /device:TPU plane
    names = {e.name for e in hosts}
    assert {tr.WINDOW, tr.STAGE_PREFIX + "scan"} <= names
    red = tr.reduce(devices, hosts)
    assert red["window_s"] > 0 and red["busy_s"] == 0
