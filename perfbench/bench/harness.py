"""One run of one cell: set-up, the measured window, the check, the result.

Set-up (``setup_s``) makes the data and the weights from the seed, builds
the ``FederatedTrainer`` as a user does (``FedConfig(sparse=True,
algorithm="fedsubavg")``), and drives it through the window's own call for
the first calls the reference follows, which also compiles (or loads from
the persistent cache) every program the window runs. The window then
repeats that call for ``--seconds``, each call ended by the server state
being ready on the device. After it, the trainer is freed and the dense
reference replays the first calls; ``check`` compares the two.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import inspect
import json
import statistics
import sys
import time
from types import SimpleNamespace

import numpy as np

from bench import check, data, reference, trace as tracing
from bench.manifest import Cell, load_cell
from bench.peaks import peaks
from bench.timing import compile_log


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def derived_seed(seed: int) -> int:
    """A 31-bit seed for the trainer and the weights, made from ``seed``."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0]
               & 0x7FFFFFFF)


def require_chips(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips needed, {len(devs)} found")
    return devs[:chips]


def _resolve(dotted: str):
    mod, name = dotted.split(":")
    return getattr(importlib.import_module(mod), name)


def make_data(cfg: dict, seed: int) -> dict:
    gen = data.GENERATORS[cfg["generator"]]
    kw = {k: cfg[k] for k in inspect.signature(gen).parameters if k in cfg}
    return gen(**kw, seed=seed)


def _feats(cohort: dict, keys) -> np.ndarray:
    """Per-client feature ids ``(K, M)``, as the trainer stacks them."""
    k = cohort[keys[0]].shape[0]
    return np.concatenate([cohort[key].reshape(k, -1) for key in keys],
                          axis=1)


def pow2_bucket(count: int, floor: int = 8) -> int:
    """The sub-id capacity the trainer buckets ``count`` distinct ids to."""
    cap = floor
    while cap < count:
        cap *= 2
    return cap


def distinct_per_client(feats: np.ndarray) -> np.ndarray:
    """Distinct non-negative ids in each row of ``feats`` ``(K, M)``."""
    f = np.sort(feats, axis=1)
    new = np.ones(f.shape, bool)
    new[:, 1:] = f[:, 1:] != f[:, :-1]
    return ((f >= 0) & new).sum(axis=1)


class Setup:
    """The trainer, its window call, and what the check needs from set-up."""

    def __init__(self, cell: Cell, seed: int, seconds: float | None = None):
        """``seconds``: the window's length, to find the sub-id buckets it
        will meet; ``None`` warms none (set-up's own calls only)."""
        import jax
        import jax.numpy as jnp

        from repro.configs import FedConfig
        from repro.core.heat import HeatStats
        from repro.data.synthetic import FederatedDataset
        from repro.federated import FederatedTrainer
        from repro.launch.mesh import make_cohort_mesh
        from repro.sharding.logical import boxed_like

        cfg, tf = cell.config, cell.traffic
        self.cell = cell
        self.s = derived_seed(seed)
        self.model = cell.model()
        self.raw = raw = make_data(cfg, seed)
        n_clients, vocab = len(raw["sample_counts"]), raw["num_features"]
        self.vocab = vocab
        ds = FederatedDataset(
            name=cfg["name"], task=cfg["reference"], num_clients=n_clients,
            num_features=vocab, client_data=raw["client_data"],
            sample_counts=raw["sample_counts"],
            heat=HeatStats(raw["heat"], float(n_clients), "vocab"),
            test_data=raw["test_data"], feature_key=raw["feature_key"])
        self.init = jax.jit(lambda key: self.model.init_params(key, cfg,
                                                                vocab))
        prog = cfg["program"]
        template = _resolve(prog["make_params"])(
            vocab, abstract=True, **{k: cfg[k] for k in prog["widths"]})
        boxed = boxed_like(self.init(jax.random.PRNGKey(self.s)), template)
        fed = FedConfig(
            num_clients=n_clients, clients_per_round=tf["clients"],
            local_iters=tf["local_iters"], local_batch=tf["local_batch"],
            lr=cfg["lr"], server_lr=cfg["server_lr"], algorithm="fedsubavg",
            sparse=True, seed=self.s)
        mesh = make_cohort_mesh(cell.chips) if tf["mesh"] == "cohort" else None
        self.tr = FederatedTrainer(ds, lambda rng=None: boxed,
                                   _resolve(prog["loss"]), fed, mesh=mesh)
        del boxed
        self.row_elems = int(np.prod(
            jax.tree.leaves(self.tr.state.params[cfg["tables"][0]])[0]
            .shape[1:]))
        if tf["driver"] == "run_rounds":
            self.rounds_per_call = tf["rounds_per_call"]
            self.call = lambda: self.tr.run_rounds(self.rounds_per_call)
        elif tf["driver"] == "run_round":
            self.rounds_per_call = 1
            self.call = lambda: [self.tr.run_round()]
        else:
            raise ValueError(f"unknown driver {tf['driver']!r}")

        # the trainer's cohort stream, replayed call by call: the sub-id
        # bucket of each call and the generator state that leads to it
        keys, cd = cfg["feature_keys"], raw["client_data"]
        self._rng = np.random.default_rng(self.s)
        self._stream = reference.cohort_stream(
            {k: cd[k] for k in keys}, raw["sample_counts"], tf["clients"],
            tf["local_iters"], tf["local_batch"], rng=self._rng)
        self._plan: list = []

        # the first calls, through the window's own entry: the reference
        # follows them, and they compile what the window runs
        x0 = [jnp.copy(x) for x in jax.tree.leaves(self.tr.state.params)]
        self.prog = {"losses": []}
        call_s = []
        for c in range(tf["check_calls"]):
            t0 = time.perf_counter()
            self.prog["losses"] += self.call()
            jax.block_until_ready(self.tr.state)
            call_s.append(time.perf_counter() - t0)
            if c in (0, tf["check_calls"] - 1):
                norms = reference.leaf_norms(
                    jax.tree.leaves(self.tr.state.params), x0)
                self.prog["first" if c == 0 else "last"] = [
                    float(v) for v in norms]
        del x0
        if seconds is not None:
            # calls after the first have compiled nothing
            per_call = min(call_s[1:] or call_s)
            self.warm(tf["check_calls"]
                      + int(np.ceil(4 * seconds / per_call)) + 1)

    def plan(self, calls: int) -> list:
        """``(generator state, sub-id bucket)`` of each of the trainer's
        first ``calls`` calls: its cohort stream (``reference.cohort_stream``
        draws what it draws; the draws do not depend on which leaves are
        kept), replayed from the seed."""
        n = self.rounds_per_call
        keys = self.cell.config["feature_keys"]
        while len(self._plan) < calls:
            state = self._rng.bit_generator.state
            most = max(int(distinct_per_client(_feats(next(self._stream),
                                                      keys)).max())
                       for _ in range(n))
            self._plan.append((state, pow2_bucket(most)))
        return self._plan[:calls]

    def capacities(self, first: int, calls: int) -> list:
        """The sub-id bucket of each round of calls ``first`` to
        ``first + calls``."""
        plan = self.plan(first + calls)[first:]
        return [cap for _, cap in plan for _ in range(self.rounds_per_call)]

    def warm(self, calls: int) -> None:
        """Compile every sub-id bucket that the first ``calls`` calls meet
        and set-up's own calls did not: one call through the window's own
        entry, on a copy of the server state, from the generator state that
        leads to that bucket. The trainer's state and generator are then
        put back, so the window goes on where set-up left it."""
        import jax
        import jax.numpy as jnp

        done = self.cell.traffic["check_calls"]
        met = {cap for _, cap in self.plan(done)}
        todo: dict = {}
        for state, cap in self.plan(calls)[done:]:
            if cap not in met:
                todo.setdefault(cap, state)
        tr = self.tr
        keep, keep_rng = tr.state, tr.np_rng.bit_generator.state
        for cap in sorted(todo):
            tr.state = jax.tree.map(jnp.copy, keep)
            tr.np_rng.bit_generator.state = todo[cap]
            self.call()
            jax.block_until_ready(tr.state)
        tr.state = keep
        tr.np_rng.bit_generator.state = keep_rng

    def free(self) -> None:
        del self.tr, self.call
        gc.collect()


def run_window(st: Setup, seconds: float, trace: bool) -> dict:
    import jax
    ann = (jax.profiler.TraceAnnotation if trace
           else lambda _: contextlib.nullcontext())
    first_round = len(st.tr.telemetry_log)
    times, rounds = [], 0
    paths = []
    rec = tracing.record() if trace else contextlib.nullcontext(paths)
    with compile_log() as clog, rec as paths:
        with ann(tracing.WINDOW):
            t0 = time.perf_counter()
            while True:
                with ann(tracing.CALL):
                    tc = time.perf_counter()
                    st.call()
                    jax.block_until_ready(st.tr.state)
                    times.append(time.perf_counter() - tc)
                rounds += st.rounds_per_call
                if time.perf_counter() - t0 >= seconds:
                    break
            elapsed = time.perf_counter() - t0
    tel = st.tr.telemetry_log[first_round:]
    caps = st.capacities(st.cell.traffic["check_calls"], len(times))
    return {"elapsed_s": elapsed, "rounds": rounds, "call_s": times,
            "compiles": len(clog["compile_s"]),
            "compile_s": sum(clog["compile_s"]),
            "capacities": caps,
            "union_size_mean": (statistics.fmean(t["union_size"] for t in tel)
                                if tel else None),
            "dropped_ids": int(sum(t["dropped_ids"] for t in tel)),
            "trace_files": paths}


def run_reference(cell: Cell, raw: dict, s: int, init, *,
                  fault: str | None = None, mm=None) -> dict:
    """The dense reference over the calls the check compares; ``mm``
    replaces its matmul (the control), ``fault`` plants a fault."""
    import functools

    import jax
    import jax.numpy as jnp
    cfg, tf = cell.config, cell.traffic
    model = cell.model()
    loss = model.loss if mm is None else functools.partial(model.loss, mm=mm)
    n_clients, vocab = len(raw["sample_counts"]), raw["num_features"]
    heat = reference.heat_counts(raw["client_data"], raw["sample_counts"],
                                 cfg["feature_keys"], vocab)
    factor = jnp.asarray(np.where(heat > 0, n_clients / np.maximum(heat, 1),
                                  0.0), jnp.float32)
    one_round = reference.make_round(
        loss, cfg["lr"], cfg["server_lr"], cfg["tables"], factor,
        tf["clients"], fault=fault, shards=cell.chips)
    stream = reference.cohort_stream(
        raw["client_data"], raw["sample_counts"], tf["clients"],
        tf["local_iters"], tf["local_batch"], s)
    params = init(jax.random.PRNGKey(s))
    x0 = jax.tree.map(jnp.copy, params)
    per_call = (tf["rounds_per_call"] if tf["driver"] == "run_rounds" else 1)
    out = {"losses": []}
    for c in range(tf["check_calls"]):
        for r in range(per_call):
            cohort = next(stream)
            if fault == "token" and c == 0 and r == 0:
                cohort = reference.alter_token(
                    cohort, cfg["feature_keys"][0], vocab)
            params, loss = one_round(params, cohort)
            out["losses"].append(float(loss))
        if c in (0, tf["check_calls"] - 1):
            out["first" if c == 0 else "last"] = [
                float(v) for v in reference.leaf_norms(params, x0)]
    return out


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _per_layer(cell: Cell, st: Setup, win: dict, kind: str) -> tuple:
    tr = tracing.load(win["trace_files"][0])
    planes = sorted(tr.devices)[:cell.chips]
    tf = cell.traffic
    cache: dict = {}

    def read(metric: str):
        if metric not in cache:
            cache[metric] = cell.reader(metric).read(ctx)
        return cache[metric]

    ctx = SimpleNamespace(
        trace=tr, devices=planes, rounds=win["rounds"], chips=cell.chips,
        config=cell.config, traffic=tf, capacities=win["capacities"],
        vocab=st.vocab, row_elems=st.row_elems, peaks=peaks(kind),
        flops_per_sample=st.model.flops_per_sample(cell.config), read=read)
    metrics = {}
    for m in cell.per_layer:
        v = read(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = [tr.busy_s(d) for d in planes]
    busiest = planes[int(np.argmax(busy))] if planes else None
    device = {"busy_s": statistics.fmean(busy) if busy else 0.0,
              "window_s": tr.window_s}
    brk = tracing.breakdown(tr, busiest) if busiest else None
    return metrics, device, brk


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, cell: Cell | None = None,
             log=sys.stderr, t_start: float | None = None) -> dict:
    """One run of the cell; returns the result object of the last line.
    Set-up is timed from ``t_start`` (the process's start, by default this
    call)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell or load_cell(name)
    import jax
    devices = (require_chips(cell.chips) if require_chip
               else jax.devices()[:cell.chips])
    kind = devices[0].device_kind
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        st = Setup(cell, seed, seconds)
        setup_s = time.perf_counter() - t_start
        win = run_window(st, seconds, trace)
        memory_peak = _memory_peak(devices)
        per_layer = _per_layer(cell, st, win, kind) if trace else None
        raw, s, init, prog = st.raw, st.s, st.init, st.prog
        st.free()
        del st
        t_ref = time.perf_counter()
        ref = run_reference(cell, raw, s, init)
        ref_s = time.perf_counter() - t_ref
    tracing.cleanup(win.pop("trace_files"))
    correct, checks = check.judge(check.gaps(prog, ref), cell.limits)

    tf = cell.traffic
    call_s = win.pop("call_s")
    caps = win.pop("capacities")
    info = {**win, "capacity": sorted(set(caps)), "setup_s": setup_s,
            "reference_s": ref_s,
            "calls": len(call_s), "call_s_median": statistics.median(call_s)}
    print(json.dumps({"window": info}), flush=True)

    k = tf["clients"]
    if trace:
        metrics, dev_extra, brk = per_layer
    else:
        # a metric split by cell, ``<base>.<part>``, is its base's number
        values = {"updates_per_s": k * win["rounds"] / win["elapsed_s"],
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"].split(".")[0]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
        dev_extra, brk = {}, None
    result = {"correct": bool(correct), "attempted": win["rounds"],
              "failed": 0, "metrics": metrics,
              "device": {"platform": devices[0].platform, "kind": kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak, **dev_extra}}
    if brk is not None:
        result["breakdown"] = brk
    result["checks"] = checks
    for n, c in checks.items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}", file=log)
    log.flush()
    return result
