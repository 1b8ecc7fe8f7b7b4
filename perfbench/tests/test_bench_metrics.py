"""Each per-layer reader gives known numbers: on a hand-made trace, and on
a short trace recorded on the chip and committed beside this file."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.manifest import load_cell
from bench.peaks import PEAKS, peaks
from bench.trace import Trace, breakdown

DATA = Path(__file__).parent / "data"
V5E = PEAKS["TPU v5 lite"]


def _ctx(tr, cell, rounds, capacity, vocab, row_elems, flops_per_sample,
         devices=None):
    cache = {}

    def read(m):
        if m not in cache:
            cache[m] = cell.reader(m).read(ctx)
        return cache[m]

    ctx = SimpleNamespace(
        trace=tr, devices=devices or sorted(tr.devices), rounds=rounds,
        chips=cell.chips, config=cell.config, traffic=cell.traffic,
        capacities=[capacity] * rounds, vocab=vocab, row_elems=row_elems,
        peaks=V5E,
        flops_per_sample=flops_per_sample, read=read)
    return ctx


def _hand_trace():
    # window [1000, 11000) ns; busy = [1000,3000) u [3500,6000) u [7000,7500)
    ops = [["fusion.1", 500, 2000], ["union_segsum", 1500, 3000],
           ["union_segsum", 3500, 5500], ["all-gather.2", 5000, 6000],
           ["copy.3", 7000, 7500], ["fusion.4", 11500, 12000]]
    host = [["bench_window", 1000, 11000], ["bench_call", 1000, 6100],
            ["bench_call", 6200, 11000]]
    return Trace({"/device:TPU:0": ops}, host, (1000, 11000))


def test_hand_trace_busy_and_matching(tmp_path):
    _hand_trace().dump(tmp_path / "t.json.gz")
    tr = Trace.read(tmp_path / "t.json.gz")
    assert tr.window_s == pytest.approx(1e-5)
    assert tr.busy_s("/device:TPU:0") == pytest.approx(5e-6)
    assert tr.matching_s("/device:TPU:0", ["union_segsum"]) == \
        pytest.approx(3.5e-6)
    assert tr.matching_s("/device:TPU:0", ["no-such-op"]) is None


def test_hand_trace_readers():
    cell = load_cell("sent140-lstm.sharded4-k128")
    ctx = _ctx(_hand_trace(), cell, rounds=2, capacity=256, vocab=1 << 20,
               row_elems=25, flops_per_sample=1e6)
    r = lambda m: cell.reader(m).read(ctx)  # noqa: E731
    assert r("device_idle_pct") == pytest.approx(50.0)
    assert r("union_ms_per_round") == pytest.approx(3.5e-6 * 1e3 / 2)
    assert r("collective_ms_per_round") == pytest.approx(1e-6 * 1e3 / 2)
    # K=128 over 4 chips: T = 32 * 256 = 8192, cap = 128 * 256 = 32768
    t, cap, d = 8192, 32768, 25
    nbytes = 4 * t + 4 * t * d + 8 * cap + 4 * cap * d
    least = nbytes / V5E["hbm_bytes_per_s"]
    assert r("union_roofline_pct") == pytest.approx(
        100 * least / (3.5e-6 / 2))
    flops = 128 * 4 * 8 * 1e6
    assert r("step_mfu_pct") == pytest.approx(
        100 * flops * (2 / 1e-5) / (4 * V5E["flops_bf16"]))


def test_readers_are_silent_with_nothing_to_read():
    cell = load_cell("sent140-lstm.engine-k64")
    tr = Trace({"/device:TPU:0": [["fusion.1", 0, 10]]}, [], (0, 100))
    ctx = _ctx(tr, cell, rounds=1, capacity=256, vocab=1 << 20,
               row_elems=25, flops_per_sample=1.0)
    for m in ("union_ms_per_round", "union_roofline_pct",
              "collective_ms_per_round"):
        assert cell.reader(m).read(ctx) is None


def test_breakdown_names_ops_and_gaps():
    b = breakdown(_hand_trace(), "/device:TPU:0")
    assert b["device_ops"][0] == ["union_segsum", pytest.approx(3.5e-6)]
    gaps = dict((round(s * 1e9), n) for n, s in b["idle_gaps"])
    # the gap [6000, 7000) lies under the first call's end and the second's
    # start; its midpoint 6500 is in the second call
    assert gaps[1000] == "bench_call"
    assert gaps[3500] == "bench_call"       # [7500, 11000)
    assert gaps[500] == "bench_call"        # [3000, 3500)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        peaks("cpu")


def test_readers_on_a_trace_recorded_on_the_chip():
    """Five ``run_round`` calls of ``din-amazon.step-k128`` on one TPU v5
    lite, cut from a traced window; the numbers were read off it once."""
    tr = Trace.read(DATA / "din-amazon.step-k128.trace.json.gz")
    cell = load_cell("din-amazon.step-k128")
    fps = cell.model().flops_per_sample(cell.config)
    assert fps == 170640.0
    ctx = _ctx(tr, cell, rounds=5, capacity=256, vocab=63001, row_elems=18,
               flops_per_sample=fps)
    dev = "/device:TPU:0"
    assert tr.window_s == pytest.approx(0.216987481)
    assert tr.busy_s(dev) == pytest.approx(0.154869019)
    got = {m: cell.reader(m).read(ctx) for m in
           ("device_idle_pct", "step_mfu_pct", "union_ms_per_round",
            "union_roofline_pct", "collective_ms_per_round")}
    assert got == pytest.approx({
        "device_idle_pct": 28.62767092079381,
        "step_mfu_pct": 0.008175416560945649,
        "union_ms_per_round": 18.8854668,
        "union_roofline_pct": 0.03304934887563282,
        "collective_ms_per_round": None}, rel=1e-9)
    # the cell's own metrics are split by driver and read by their base's
    # reader
    assert {m["name"]: cell.reader(m["name"]).read(ctx)
            for m in cell.per_layer} == {
        f"{m}.step": got[m] for m in got if got[m] is not None}
    b = breakdown(tr, dev)
    assert b["device_ops"][0][0] == "union_segsum.1 (custom-call)"
    assert len(b["idle_gaps"]) == 10
