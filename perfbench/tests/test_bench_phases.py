"""The readers of the program's own spans and scopes give known numbers: on
a hand-made trace, on a short trace recorded on the chip with the spans and
committed beside this file, and on a traced CPU run; and they are silent on
a trace without the spans."""
import copy
import glob
import io
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import scopes
from bench import trace as tracing
from bench.manifest import load_cell
from bench.peaks import PEAKS
from bench.scopes import ScopedTrace
from bench.trace import Trace, breakdown

DATA = Path(__file__).parent / "data"
V5E = PEAKS["TPU v5 lite"]
#: the readers of the program's own spans and scopes
PHASE_READERS = ["host_prep_ms_per_round", "sync_wait_ms_per_round",
                 "host_syncs_per_round", "local_ms_per_round",
                 "apply_ms_per_round", "telemetry_ms_per_round"]


def _ctx(tr, cell, rounds, capacity, vocab, row_elems, flops_per_sample):
    cache = {}

    def read(m):
        if m not in cache:
            cache[m] = cell.reader(m).read(ctx)
        return cache[m]

    ctx = SimpleNamespace(
        trace=tr, devices=sorted(tr.devices), rounds=rounds,
        chips=cell.chips, config=cell.config, traffic=cell.traffic,
        capacities=[capacity] * rounds, vocab=vocab, row_elems=row_elems,
        peaks=V5E, flops_per_sample=flops_per_sample, read=read)
    return ctx


def _phase_trace():
    """Two calls with the program's spans, and device operations with
    scope paths; window [1000, 11000), two rounds."""
    ops = [["fusion.1 (fusion)", 1000, 2000],
           ["while.2 (while)", 1000, 3000],
           ["union_segsum.3 (custom-call)", 2000, 3000],
           ["fusion.4 (fusion)", 3500, 4000],
           ["fusion.5 (fusion)", 4000, 4200],
           ["copy.6 (copy)", 4200, 4300],
           ["fusion.7 (fusion)", 7000, 7500],
           ["fusion.8 (fusion)", 10800, 11500]]
    paths = ["jit(step)/fedsub.local/dot_general",
             "jit(step)/fedsub.local/while",
             "jit(step)/fedsub.aggregate/union_segsum",
             "jit(step)/fedsub.apply/add",
             "jit(step)/fedsub.telemetry/reduce_sum",
             None,
             "jit(count_sub_ids)/sort",
             "jit(step)/fedsub.local/mul"]
    host = [["bench_window", 1000, 11000]]
    for c0, c1 in ((1000, 6000), (6000, 11000)):
        host += [["bench_call", c0, c1],
                 [f"fedsub.call#driver=run_round,first_round={c0}#", c0, c1]]
    host += [["fedsub.sample", 1000, 1500],
             ["fedsub.sub_ids", 1500, 2500], ["fedsub.sync", 2000, 2400],
             ["fedsub.dispatch", 2500, 2800],
             ["fedsub.account", 3000, 5000], ["fedsub.sync", 3100, 3600],
             ["fedsub.sync", 4500, 4800],
             ["fedsub.sync", 5000, 5500],
             ["fedsub.sample", 6000, 6600],
             ["fedsub.sub_ids", 6600, 7600], ["fedsub.sync", 7000, 7200],
             ["fedsub.dispatch", 7600, 8000],
             ["fedsub.account", 8000, 10500], ["fedsub.sync", 8200, 8600],
             ["fedsub.sync", 10500, 10900]]
    dev = "/device:TPU:0"
    return ScopedTrace({dev: ops}, host, (1000, 11000), {dev: paths})


#: the hand-computed value of each phase reader on ``_phase_trace``, in ms
#: (busy [1000,3000) u [3500,4300) u [7000,7500) u [10800,11000))
PHASE_HAND = {
    # prep 500+1000+2000 + 600+1000+2500, less the syncs they hold
    # 400+500+300 + 200+400 (the loss pulls lie outside them)
    "host_prep_ms_per_round": (7600 - 1800) * 1e-6 / 2,
    # idle under syncs: 400 + 300 + 500 + 0 + 400 + 300
    "sync_wait_ms_per_round": 1900 * 1e-6 / 2,
    "host_syncs_per_round": 7 / 2,
    # the while holds its body and is left out; fusion.8 is clipped to 200
    "local_ms_per_round": (1000 + 200) * 1e-6 / 2,
    "apply_ms_per_round": 500 * 1e-6 / 2,
    "telemetry_ms_per_round": 200 * 1e-6 / 2,
}


@pytest.mark.parametrize("metric", PHASE_READERS)
def test_phase_readers_on_a_hand_trace(metric):
    cell = load_cell("sent140-lstm.engine-k64")
    ctx = _ctx(_phase_trace(), cell, rounds=2, capacity=256, vocab=1 << 20,
               row_elems=25, flops_per_sample=1.0)
    assert cell.reader(metric).read(ctx) == pytest.approx(PHASE_HAND[metric])
    # a name split by cell reads through the same reader
    din = load_cell("din-amazon.step-k128")
    assert din.reader(f"{metric}.step").read(ctx) == \
        pytest.approx(PHASE_HAND[metric])


@pytest.mark.parametrize("metric", PHASE_READERS)
def test_phase_readers_are_silent_without_program_spans(metric):
    """The trace recorded before the program had spans or scopes."""
    tr = ScopedTrace.read(DATA / "din-amazon.step-k128.trace.json.gz")
    assert tr.scopes == {}
    cell = load_cell("din-amazon.step-k128")
    ctx = _ctx(tr, cell, rounds=5, capacity=256, vocab=63001, row_elems=18,
               flops_per_sample=1.0)
    assert cell.reader(f"{metric}.step").read(ctx) is None
    # as a plain trace, which has no profile file to go back to
    ctx.trace = Trace.read(DATA / "din-amazon.step-k128.trace.json.gz")
    assert cell.reader(f"{metric}.step").read(ctx) is None


def test_trace_dump_and_read_carry_scopes(tmp_path):
    tr = _phase_trace()
    tr.dump(tmp_path / "t.json.gz")
    back = ScopedTrace.read(tmp_path / "t.json.gz")
    assert back.scopes == tr.scopes
    assert (back.devices, back.host, back.window) == \
        (tr.devices, tr.host, tr.window)
    # the benchmark's own reader takes the same file, without the scopes
    plain = Trace.read(tmp_path / "t.json.gz")
    assert (plain.devices, plain.host, plain.window) == \
        (tr.devices, tr.host, tr.window)


def test_scope_paths_come_from_the_traces_hlo(tmp_path):
    """The HLO the profiler keeps in its metadata plane names each
    instruction's scope path; an operation takes it from the program run
    that holds it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        with jax.named_scope("fedsub.local"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("fedsub.apply"):
            return y + 1

    x = jnp.ones((8, 8))
    probe(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    probe(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    programs = {k: v for k, v in scopes.hlo_op_names(path).items()
                if k.startswith("jit_probe(")}
    assert len(programs) == 1
    (name, ops), = programs.items()
    paths = {p for p in ops.values() if p}
    assert any("/fedsub.local/" in p for p in paths)
    assert any("/fedsub.apply/" in p for p in paths)
    inst = next(i for i, p in ops.items() if p and "fedsub.apply" in p)
    got = scopes.op_scopes([[f"%{inst} = f32[8,8] add(...)", 150, 160],
                            [f"%{inst} = f32[8,8] add(...)", 250, 260]],
                           [[name, 100, 200]], {name: ops})
    assert got == [ops[inst], None]          # the second lies in no run


def test_scopes_go_back_to_the_profile_the_trace_was_read_from():
    """A trace reduced by the benchmark keeps no scopes; they are read from
    the profile file ``trace.record`` left, found by the window span."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    with tracing.record() as paths:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            f(x).block_until_ready()
    try:
        tr = tracing.load(paths[0])
        assert tr.window[1] > tr.window[0]
        assert scopes.xplane_of(tr) == paths[0]
        # the CPU has no device plane the benchmark reads
        assert scopes.of(tr) == scopes.load(paths[0]) == {}
    finally:
        tracing.cleanup(paths)
    assert scopes.xplane_of(Trace({}, [], (1, 2))) is None
    assert scopes.of(Trace({}, [], (1, 2))) == {}


#: the syncs a round of each cell, as the trainer pulls them: the sub-id
#: counts, nine telemetry fields and the loss, once a call
SYNCS = {"din-amazon.step-k128": 11.0, "sent140-lstm.engine-k64": 1.1}


@pytest.mark.parametrize("name", sorted(SYNCS))
def test_program_spans_in_a_traced_cpu_run(name, monkeypatch):
    """A traced run of the cell cut to CPU size: the host-span readers read
    the program's spans; the CPU trace has no device plane, so the
    device-side readers stay silent. The DIN cell reads them under the
    names split by its driver."""
    from bench import harness
    from tiny import SEED, tiny_cell
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS["TPU v5 lite"])
    cell = tiny_cell(name)
    suffix = ".step" if cell.traffic["driver"] == "run_round" else ""
    have = {m["name"] for m in cell.per_layer}
    for metric in PHASE_READERS:
        if metric + suffix not in have:
            entry = copy.deepcopy(next(m for m in load_cell(
                "sent140-lstm.engine-k64").per_layer if m["name"] == metric))
            entry.update(name=metric + suffix, moves="updates_per_s" + suffix)
            cell.per_layer.append(entry)
    res = harness.run_cell(name, SEED, 0.3, True, require_chip=False,
                           cell=cell, log=io.StringIO())
    m = res["metrics"]
    assert m["host_syncs_per_round" + suffix]["value"] == \
        pytest.approx(SYNCS[name])
    assert m["host_prep_ms_per_round" + suffix]["value"] > 0
    for metric in ("sync_wait_ms_per_round", "local_ms_per_round",
                   "apply_ms_per_round", "telemetry_ms_per_round"):
        assert metric + suffix not in m


def test_readers_on_a_trace_with_program_spans():
    """Five ``run_round`` calls of ``din-amazon.step-k128`` on one TPU v5
    lite, traced with the program's spans and scopes and cut as the trace
    recorded before them; every reader's number was read off it once. The
    phase readers agree with what the whole traced window printed on the
    chip: 11 pulls a round; local 5.368, apply 0.362, telemetry 5.351 ms a
    round."""
    tr = ScopedTrace.read(DATA / "din-amazon.step-k128.spans.trace.json.gz")
    cell = load_cell("din-amazon.step-k128")
    ctx = _ctx(tr, cell, rounds=5, capacity=256, vocab=63001, row_elems=18,
               flops_per_sample=cell.model().flops_per_sample(cell.config))
    dev = "/device:TPU:0"
    assert len(tr.scopes[dev]) == len(tr.devices[dev])
    got = {m["name"]: cell.reader(m["name"]).read(ctx)
           for m in cell.per_layer}
    got.update({f"{m}.step": cell.reader(f"{m}.step").read(ctx)
                for m in PHASE_READERS})
    assert got == pytest.approx({
        "device_idle_pct.step": 28.70157730535211,
        "step_mfu_pct.step": 0.00816628800595048,
        "union_ms_per_round.step": 18.885466,
        "union_roofline_pct.step": 0.03304935027562365,
        "host_prep_ms_per_round.step": 4.1431356,
        "sync_wait_ms_per_round.step": 6.4215942,
        "host_syncs_per_round.step": 11.0,
        "local_ms_per_round.step": 5.367671,
        "apply_ms_per_round.step": 0.362023,
        "telemetry_ms_per_round.step": 5.3510046}, rel=1e-9)
    # the gaps between rounds are named by the program's spans now
    names = {n for n, _ in breakdown(tr, dev)["idle_gaps"]}
    assert {"fedsub.account", "fedsub.sample"} <= names
