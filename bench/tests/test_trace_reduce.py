"""The reduction from a trace to the per-layer metrics: on a hand-made
trace whose answers are known, and on a slice of a trace recorded on the
chip (``bench/tests/fixtures``)."""
import gzip
import json
import os

import pytest

from bench import run, trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MS = 1e6  # nanoseconds


def group(name, scope="", kernel=False, container=False):
    return {"name": name, "scope": scope, "kernel": kernel,
            "container": container}


def hand_trace():
    """Two devices, a 100 ms window. Device 0: input 0-10 ms, a loop op
    spanning 10-80 ms over local ops 10-40 and 45-60, the kernel 60-70
    under the meta mix, an all-reduce 68-75 (70-75 alone), idle 75-100.
    Device 1 the same without the input."""
    groups = [group("local: dot", "obs.local_phase"),
              group("local: while", "obs.local_phase", container=True),
              group("mix: kernel", "obs.meta_mix", kernel=True),
              group("mix: all-reduce", "obs.meta_mix"),
              group("input: sample", "")]
    d0 = [[0, 10 * MS, 4], [10 * MS, 80 * MS, 1], [10 * MS, 40 * MS, 0],
          [45 * MS, 60 * MS, 0], [60 * MS, 70 * MS, 2],
          [68 * MS, 75 * MS, 3]]
    d1 = [op for op in d0 if op[2] != 4]
    return {"window": [0, 100 * MS], "groups": groups,
            "devices": {"0": {"ops": d0, "modules": [
                [0, 10 * MS, "jit_sample_lm"],
                [10 * MS, 80 * MS, "jit_fused"]]},
                        "1": {"ops": d1, "modules": []}},
            "host": [[0, 100 * MS, "bench.window"],
                     [80 * MS, 90 * MS, "obs.host_flush"],
                     [90 * MS, 100 * MS, "obs.dispatch"]]}


def test_union_ignores_loop_ops_and_overlaps():
    t = hand_trace()
    busy = tr.busy_seconds(t)
    # 0-40 and 45-75 on device 0; 10-40 and 45-75 on device 1
    assert busy == {"0": pytest.approx(0.070), "1": pytest.approx(0.060)}
    assert tr.window_seconds(t) == pytest.approx(0.1)
    local = lambda g: g["scope"] == "obs.local_phase"
    assert tr.group_seconds(t, "0", local) == pytest.approx(0.045)
    assert tr.module_seconds(t, "0", "jit_sample_lm") == pytest.approx(0.01)
    mix = lambda g: g["scope"] == "obs.meta_mix"
    assert tr.group_seconds(t, "0", mix) == pytest.approx(0.015)


def test_idle_gaps_are_named_by_the_host():
    b = tr.breakdown(hand_trace())
    gaps = dict((round(s, 6), n) for n, s in b["idle_gaps"])
    assert gaps == {0.025: "obs.host_flush", 0.005: "no span"}
    names = [n for n, _ in b["device_ops"]]
    assert "local: while" not in names and names[0] == "local: dot"


def test_metric_readers_on_the_hand_trace():
    t = hand_trace()
    ctx = {"steps": 1, "chips": 2, "tokens_per_s": 1000.0,
           "config": {**run.load_cell("xlstm350m_1chip_k8")["config"],
                      "learners": 4},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: run.metric_reader(name)(t, ctx)
    assert read("input.ms_per_step") == (pytest.approx(10.0), "ms")
    assert read("local_phase.ms_per_step") == (pytest.approx(45.0), "ms")
    assert read("meta_mix.ms_per_step") == (pytest.approx(15.0), "ms")
    assert read("device.idle_share") == (pytest.approx(35.0), "%")
    share, unit = read("fused_meta_roofline")
    assert unit == "%" and share > 100  # 11 GB in 10 ms: beyond the peak
    mfu, _ = read("step.mfu")
    assert mfu == pytest.approx(100 * 2962685952 * 3 / 3 * 1000
                                / (2 * 197e12), rel=1e-3)


def test_a_reader_with_nothing_to_read_returns_nothing():
    t = hand_trace()
    t["devices"]["0"]["modules"] = []
    t["groups"][2]["kernel"] = False
    ctx = {"steps": 1, "chips": 2, "config": {
        **run.load_cell("xlstm350m_1chip_k8")["config"], "learners": 4},
        "peaks": {"hbm_bytes_per_s": 819e9}}
    assert run.metric_reader("input.ms_per_step")(t, ctx) is None
    assert run.metric_reader("fused_meta_roofline")(t, ctx) is None


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(FIXTURES) if f.endswith(".json.gz"))
    if os.path.isdir(FIXTURES) else [])
def test_recorded_trace(name):
    """A slice recorded on the chip by a traced run of cell 1: 55 ms at
    the end of a meta step, the meta mix and the next batches."""
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        t = json.load(f)
    window = tr.window_seconds(t)
    for dev, busy in tr.busy_seconds(t).items():
        assert 0 < busy <= window
        mix = tr.group_seconds(t, dev, lambda g: g["scope"] == "obs.meta_mix")
        kern = tr.group_seconds(
            t, dev, lambda g: g["kernel"] and g["scope"] == "obs.meta_mix")
        assert 0 < kern <= mix <= busy
    b = tr.breakdown(t)
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert all(s > 0 for _, s in b["idle_gaps"])
