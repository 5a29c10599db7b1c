"""benchmark/lib/trace_reduce.py against recorded v5e traces.

`data/*.xplane.pb.gz` were written by `jax.profiler` on the chip
(record_trace.py: a preset cell at toy sizes through the real harness,
`device_kind` "TPU v5 lite"); the `.json` beside each holds the run's
kernel map and device.  The expected numbers below were worked out
from the files' events apart from the code under test: window and
spans read off the host plane, busy time by a sweep over the op
events' end points, kernel and collective sums by adding up the events
of those names by hand.  The interval arithmetic is also checked on
cases small enough to do in the head.
"""

import gzip
import json
import os
import shutil

import pytest

from benchmark.lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def recorded(name, tmp_path):
    path = tmp_path / (name + ".xplane.pb")
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(DATA, name + ".json")) as f:
        meta = json.load(f)
    return tr.load(str(path)), meta


def sweep_busy_ns(ops, lo, hi):
    """Busy time the slow way: walk the sorted end points of the
    clipped events and add up the stretches with at least one open."""
    points = []
    for _, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    depth = busy = 0
    last = None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    return busy


# -- interval arithmetic, in the head ----------------------------------------

def test_union_minus_length():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == \
        [[0, 3], [5, 8]]
    assert tr._length([(0, 3), (5, 8)]) == 6
    assert tr._minus([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr._minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr._minus([(0, 4)], []) == [(0, 4)]


def test_instruction_name():
    assert tr.instruction_name(
        "%fusion.8 = bf16[1024,256]{1,0} fusion(bf16[8] %p), kind=kLoop") \
        == "fusion.8"
    assert tr.instruction_name("copy-done.95") == "copy-done.95"


def test_reduce_on_events_made_by_hand():
    """One chip, window [0, 100) us: two ops overlapping, a kernel, an
    asynchronous all-reduce half hidden behind a fusion, and a gap the
    host spent in `bench.fetch`."""
    us = 1000                                          # events are in ns
    events = {
        "devices": {"/device:TPU:0": [
            (n, s * us, e * us) for n, s, e in [
                ("fusion.1", 0, 30), ("fusion.2", 20, 40),
                ("kern.5", 40, 50),
                ("all-reduce-start.1", 50, 51),
                ("fusion.3", 51, 60),
                ("all-reduce-done.1", 60, 70),
                ("fusion.4", 90, 100)]]},
        "spans": [(n, s * us, e * us) for n, s, e in [
            ("bench.window", 0, 100), ("bench.dispatch", 0, 5),
            ("bench.fetch", 65, 95)]],
    }
    r = tr.reduce(events, steps=2, kernel_ops={"kern.5": "k"})
    assert r["window_s"] == 100e-6
    assert r["busy_s"] == pytest.approx(80e-6)       # [0, 70) + [90, 100)
    assert r["kernel_s"] == {"k": pytest.approx(10e-6)}
    assert r["collective_s"] == pytest.approx(20e-6)           # [50, 70)
    assert r["collective_exposed_s"] == pytest.approx(11e-6)   # less [51, 60)
    assert r["idle_gaps"] == [["bench.fetch", pytest.approx(20e-6)]]
    assert r["device_ops"][0] == ["fusion.* x2", pytest.approx(69e-6)]
    assert tr.reduce({"devices": {}, "spans": []}, steps=1) is None


# -- the recorded one-chip trace ------------------------------------------------

def test_one_chip_trace(tmp_path):
    events, meta = recorded("bert_small.pretrain", tmp_path)
    assert list(events["devices"]) == ["/device:TPU:0"]
    ops = events["devices"]["/device:TPU:0"]
    assert len(ops) == 2728
    window = [s for s in events["spans"] if s[0] == tr.WINDOW_SPAN]
    assert window == [("bench.window", 45419516.0, 57638494.0)]
    # 4 steps in 2 chunks: 4 batches, feeds and dispatches, 2 fetches
    names = [s[0] for s in events["spans"]]
    assert [names.count(n) for n in ("bench.next_batch", "bench.feed",
                                     "bench.dispatch", "bench.fetch")] \
        == [4, 4, 4, 2]

    r = tr.reduce(events, meta["traced_steps"], meta["kernel_ops"])
    assert r["chips"] == 1 and r["steps"] == 4
    assert r["window_s"] == pytest.approx(0.012218978, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.001238684, rel=1e-9)
    assert r["busy_s"] * 1e9 == pytest.approx(
        sweep_busy_ns(ops, window[0][1], window[0][2]), rel=1e-12)
    # 2 layers x 4 steps: 8 forward calls, 16 backward calls
    fwd = [e - s for n, s, e in ops if meta["kernel_ops"].get(n) == "flash_fwd"]
    bwd = [e - s for n, s, e in ops if meta["kernel_ops"].get(n) == "flash_bwd"]
    assert (len(fwd), len(bwd)) == (8, 16)
    assert r["kernel_s"]["flash_fwd"] == pytest.approx(sum(fwd) / 1e9)
    assert r["kernel_s"]["flash_fwd"] == pytest.approx(7.1023e-05, rel=1e-6)
    assert r["kernel_s"]["flash_bwd"] == pytest.approx(0.000106912, rel=1e-6)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    # a toy model waits for its host: the chip idles nine tenths of it
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.8986, abs=1e-4)
    assert r["device_ops"][0] == ["fusion.* x134",
                                  pytest.approx(0.00062378, rel=1e-6)]
    assert [g[0] for g in r["idle_gaps"]] == ["bench.dispatch", "bench.feed"]
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=2e-3)  # gaps under 1 us left out


# -- the recorded four-chip trace (slimmed: see record_trace.py) ------------------

def test_four_chip_trace(tmp_path):
    events, meta = recorded("resnet_tiny.train_dp4", tmp_path)
    assert sorted(events["devices"]) == [f"/device:TPU:{i}" for i in range(4)]
    window = [s for s in events["spans"] if s[0] == tr.WINDOW_SPAN]
    assert window == [("bench.window", 162044988.0, 202939405.0)]
    lo, hi = window[0][1:]

    r = tr.reduce(events, meta["traced_steps"], meta["kernel_ops"])
    assert r["chips"] == 4 and r["kernel_s"] == {}
    assert r["window_s"] == pytest.approx(0.040894417, rel=1e-9)
    # busy: the mean over the chips of each chip's own union
    per_chip = [sweep_busy_ns(ops, lo, hi)
                for ops in events["devices"].values()]
    assert r["busy_s"] * 1e9 == pytest.approx(sum(per_chip) / 4, rel=1e-12)
    assert r["busy_s"] == pytest.approx(0.00213923575, rel=1e-9)
    # the {data: 4} step makes 100 synchronous all-reduces (gradients
    # and batch-norm statistics); added up by hand per chip, in ns
    by_hand = {"/device:TPU:0": 1514794, "/device:TPU:1": 1511592,
               "/device:TPU:2": 1507827, "/device:TPU:3": 1508379}
    for chip, ops in events["devices"].items():
        calls = [e - s for n, s, e in ops
                 if n.startswith("all-reduce.") and s >= lo and e <= hi]
        assert len(calls) == 400                     # 100 a step, 4 steps
        assert sum(calls) == pytest.approx(by_hand[chip])
    assert r["collective_s"] == pytest.approx(
        sum(by_hand.values()) / 4 / 1e9, rel=1e-9)
    # a synchronous collective holds the core: all of it is exposed
    assert r["collective_exposed_s"] == pytest.approx(r["collective_s"])
    assert r["device_ops"][0] == ["all-reduce.* x100",
                                  pytest.approx(0.001510648, rel=1e-6)]
    assert r["idle_gaps"][0][0] == "bench.dispatch"
