"""The trace reduction: busy union, idle share, named operation time and
idle gaps, on a small trace of known numbers.

    python -m pytest -q chipbench/tests/test_devtrace.py
"""
import gzip
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

from bench import devtrace  # noqa: E402

MS = 1_000_000   # ns

HLO = """
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(run_one)/while/body/vmap(jit(eigh))/mul"}
  %while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b, metadata={op_name="jit(run_one)/while/body/vmap(jit(eigh))/eigh"}
  ROOT %custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %y), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_one)/pallas_call"}, backend_config={"custom_call_config": {"name": "_update_kernel"}}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop, metadata={op_name="jit(run_one)/add"}
"""
CATEGORIES = devtrace.categories_from_hlo([HLO])


def small_trace():
    """Two devices over a 100 ms window.  Device 0: ops at [0, 10), [5, 20)
    (overlapping) and [50, 60) ms: busy 30 ms.  Device 1: [0, 50) ms: busy
    50 ms.  The host's python thread pulls over [20, 50) ms."""
    devices = {
        "/device:TPU:0": {
            "ops": [("while.1", 0, 10 * MS),
                    ("fusion.4", 2 * MS, 3 * MS),       # inside while.1
                    ("custom-call.2", 5 * MS, 15 * MS),
                    ("fusion.3", 50 * MS, 10 * MS)],
            "modules": [("jit_run_one(7)", 0, 60 * MS)]},
        "/device:TPU:1": {
            "ops": [("while.1", 0, 50 * MS)],
            "modules": [("jit_run_one(7)", 0, 50 * MS)]},
    }
    host = [("device_get", 20 * MS, 30 * MS), ("dispatch", 60 * MS, 1 * MS)]
    return devtrace.reduce_events(devices, host, window_s=0.1,
                                  categories=CATEGORIES)


def test_busy_is_the_union_averaged_over_devices():
    r = small_trace()
    assert r["busy_s"] == pytest.approx((0.030 + 0.050) / 2)
    assert devtrace.idle_percent(r) == pytest.approx(60.0)


def test_categories_come_from_the_hlo_metadata():
    assert CATEGORIES == {"fusion.4": "eigh", "while.1": "eigh",
                          "custom-call.2": "cma_gen_update"}


def test_category_and_module_time():
    r = small_trace()
    # eigh: the union of while.1 and the fusion nested in it, 10 ms on
    # device 0 and 50 ms on device 1, averaged over the devices
    assert devtrace.category_seconds(r, "eigh") == pytest.approx(0.030)
    assert devtrace.category_seconds(r, "cma_gen_update") == pytest.approx(
        0.0075)
    assert devtrace.module_seconds(r, devtrace.SEGMENT_MODULE) == \
        pytest.approx(0.055)
    assert devtrace.category_seconds(r, "cma_gen_sample") is None


def test_idle_gaps_are_named_by_the_host():
    r = small_trace()
    (where, length), = r["idle_gaps"]
    assert where == "/device:TPU:0: device_get"
    assert length == pytest.approx(0.030)
    b = devtrace.breakdown(r)
    # loops are left out of the operation list: they hold the others
    assert b["device_ops"][0][0] == "custom-call.2 [cma_gen_update]"
    assert all(not k.startswith("while") for k, _v in b["device_ops"])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_nothing_to_read_gives_nothing():
    r = devtrace.reduce_events({}, [], window_s=1.0)
    assert devtrace.idle_percent(r) is None
    assert devtrace.category_seconds(r, "eigh") is None


DATA = os.path.join(HERE, "data")


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (``chipbench/tools/record_trace.py``):
    three calls of a step holding the fused update kernel and a float64
    ``eigh`` at n = 40 on two slots."""
    with gzip.open(os.path.join(DATA, "step.hlo.txt.gz"), "rt") as fh:
        cats = devtrace.categories_from_hlo([fh.read()])
    assert "eigh" in cats.values() and "cma_gen_update" in cats.values()
    r = devtrace.reduce_dir(DATA, window_s=1.0, n_devices=1, categories=cats)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < 1.0
    eigh = devtrace.category_seconds(r, "eigh")
    update = devtrace.category_seconds(r, "cma_gen_update")
    assert eigh and update and eigh + update <= r["busy_s"] * (1 + 1e-9)
    # every operation runs inside one of the three calls of the step
    assert r["busy_s"] <= devtrace.module_seconds(r, "step") * (1 + 1e-9)
