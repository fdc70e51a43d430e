"""trace_reduce.py on a synthetic trace and on a small recorded one."""

import json
import os

import pytest

from benchmarks import trace_reduce

US = 1000


def synthetic():
    """Two devices, window 0..100 us.  Device 0: a loop (10..60) that
    encloses a fusion (10..30) and an all-reduce (30..50, alone: exposed),
    then a fusion (70..90).  Device 1: one fusion 0..100."""
    dev0 = [
        ["while.1", 10 * US, 50 * US],
        ["fusion.1", 10 * US, 20 * US],
        ["all-reduce.1", 30 * US, 20 * US],
        ["fusion.2", 70 * US, 20 * US],
    ]
    host = [
        ["bench:window", 0, 100 * US],
        ["bench:data_wait", 0, 10 * US],
        ["bench:dispatch", 60 * US, 10 * US],
        ["bench:settle", 90 * US, 10 * US],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": dev0},
            {"name": "XLA Modules",
             "events": [["jit_step(1)", 10 * US, 80 * US]]},
        ]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.9", 0, 100 * US]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]}


def test_busy_idle_and_window():
    r = trace_reduce.reduce(synthetic(), 2)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s_each"] == pytest.approx([70e-6, 100e-6])
    assert r["busy_s"] == pytest.approx(85e-6)


def test_self_time_per_name_leaves_the_loop_its_own_share():
    r = trace_reduce.reduce(synthetic(), 1)
    assert r["op_self_s"]["while.1"] == pytest.approx(10e-6)
    assert r["op_self_s"]["fusion.1"] == pytest.approx(20e-6)
    assert trace_reduce.op_seconds(r, "fusion") == pytest.approx(40e-6)
    assert r["module_s"]["jit_step(1)"] == [1, pytest.approx(80e-6)]


def test_exposed_collective():
    r = trace_reduce.reduce(synthetic(), 1)
    assert r["collective_s"] == pytest.approx(20e-6)
    assert r["exposed_collective_s"] == pytest.approx(20e-6)
    hidden = synthetic()
    hidden["planes"][0]["lines"][0]["events"].append(
        ["fusion.3", 30 * US, 15 * US]
    )
    r = trace_reduce.reduce(hidden, 1)
    assert r["exposed_collective_s"] == pytest.approx(5e-6)


def test_idle_gaps_are_named_by_the_host_span():
    r = trace_reduce.reduce(synthetic(), 1)
    gaps = dict(r["idle_gaps"])
    assert gaps["data_wait"] == pytest.approx(10e-6)
    assert gaps["dispatch"] == pytest.approx(10e-6)
    assert gaps["settle"] == pytest.approx(10e-6)


def test_interval_arithmetic():
    assert trace_reduce.union([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    assert trace_reduce.subtract([[0, 10]], [[2, 3], [5, 12]]) == [
        [0, 2], [3, 5]
    ]


def test_recorded_trace():
    """A slice of a real trace of the train cell on a TPU v5e (PR 24):
    the reader finds the device plane, the kernels and the host spans."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_trace.json")
    with open(path) as fh:
        rec = json.load(fh)
    r = trace_reduce.reduce(rec["trace"], 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-6)
    assert r["top_ops"] and r["top_ops"][0][1] > 0
