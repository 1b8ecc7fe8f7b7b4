"""The round's spans and counters, on the profiler's clock.

Host spans are ``jax.profiler.TraceAnnotation``\\ s and device phases are
``jax.named_scope``\\ s, so both land in the profiler's own ``.xplane.pb``
on the same clock as the device operations: every idle gap of the device
can be put down to a phase of the round. With no profiler running a span
costs about a microsecond, so spans are always on.

Host spans (one vocabulary for every driver):

``fedsub.call``      one ``run_round``/``run_rounds``/``run_async`` call;
                     args ``driver``, ``first_round`` (the global number of
                     its first round, which identifies the call) and
                     ``rounds``
``fedsub.chunk``     one between-evaluations stretch of ``run()``
``fedsub.sample``    one cohort drawn on the host
``fedsub.sub_ids``   feature ids to the device, ``count_sub_ids``, its
                     pull, ``pow2_capacity`` and ``derive_sub_ids``
``fedsub.dispatch``  the cohort to the device and the jitted call
``fedsub.sync``      one blocking device-to-host pull (:func:`host_pull`);
                     arg ``what``: ``count``, ``loss``, ``telemetry.<field>``
``fedsub.account``   comm accounting, telemetry to host, the sink event

Device scopes of the round step (``build_round_step``): ``fedsub.local``
(local training), ``fedsub.aggregate`` (compression, union, segment-sum,
heat correction, the cross-shard combine), ``fedsub.apply`` (the server
update), ``fedsub.loss`` (the monitoring forward pass) and
``fedsub.telemetry`` (the in-jit counters).

Counters (:func:`counters`): ``host_syncs``, the pulls made by
:func:`host_pull`, and ``compiles``, XLA backend compiles from JAX's
monitoring events (``cache_loads`` counts persistent-cache hits beside
them). They are process-wide, as JAX's compile cache is.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import numpy as np

CALL = "fedsub.call"
CHUNK = "fedsub.chunk"
SAMPLE = "fedsub.sample"
SUB_IDS = "fedsub.sub_ids"
DISPATCH = "fedsub.dispatch"
SYNC = "fedsub.sync"
ACCOUNT = "fedsub.account"

LOCAL = "fedsub.local"
AGGREGATE = "fedsub.aggregate"
APPLY = "fedsub.apply"
LOSS = "fedsub.loss"
TELEMETRY = "fedsub.telemetry"

#: JAX's monitoring events behind the compile counters
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_counts = {"host_syncs": 0, "compiles": 0, "cache_loads": 0}


def _bump(name: str) -> None:
    with _lock:
        _counts[name] += 1


def _on_duration(event: str, secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        _bump("compiles")


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _bump("cache_loads")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def counters() -> Dict[str, int]:
    """The process's counts so far: ``host_syncs``, ``compiles``,
    ``cache_loads``. Subtract two readings for the counts between them."""
    with _lock:
        return dict(_counts)


def span(name: str, **args):
    """A host span ``name`` with ``args`` as its metadata."""
    return jax.profiler.TraceAnnotation(name, **args)


def host_pull(x, what: str) -> np.ndarray:
    """Pull ``x`` to the host, waiting for it: a ``fedsub.sync`` span, and
    one more on the ``host_syncs`` counter."""
    with span(SYNC, what=what):
        out = np.asarray(jax.device_get(x))
    _bump("host_syncs")
    return out
