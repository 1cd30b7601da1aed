"""Low-overhead span tracer and device→host pull helper for the
host-sequenced round engine.

The round engine is host code sequencing jitted phase kernels, so tracing
lives entirely on the host: a span wraps one phase's kernel launch and
fences (``jax.block_until_ready``) before taking the end timestamp, so the
recorded duration covers the device work, not just the dispatch.

Both ``Tracer.span`` and :func:`pull` also put the program's names on the
profiler's clock: while a ``jax.profiler`` session is active (and only
then, tested by ``TraceAnnotation.is_enabled()``), a span opens the
annotation ``repro.<span name>`` and a pull ``repro.pull.<site>``, whether
the tracer is enabled or not.  A device trace then names the program phase
and the pull that each idle gap sits in, with no fence added.

Overhead contract (pinned by ``tests/test_obs.py``):

  * **Disabled** (``enabled=False``, or no tracer installed on the
    holder) with no profiler session: ``span()`` returns a shared no-op
    context manager; ``fence`` is the identity; NO ``block_until_ready``
    is ever issued and nothing is recorded.  Because the tracer never
    appears inside ``jax.jit``, the jitted round lowers to byte-identical
    HLO with tracing on or off — zero added device ops, zero recompiles.
  * **Disabled, profiler session active**: one ``TraceAnnotation`` per
    span; still no fence and no recorded event.
  * **Enabled**: one ``perf_counter`` pair + one list append per span,
    plus the explicit fences (and the annotation while profiling).
    Fencing serializes host/device overlap, so an enabled tracer is a
    measurement tool, not a production default.
  * **pull**: per device array pulled for the first time, one id entry
    (dropped with the array), one ``is_enabled()`` check and two counter
    increments (``host_syncs`` += 1, ``d2h_bytes`` += the array's bytes)
    in the holder's ``MetricsRegistry``; a repeated pull of the same
    array reads its host copy and counts nothing.

Events use the Chrome trace-event model (complete events ``ph="X"``,
instants ``ph="i"``) so export is a dump, not a transform — see
``repro.obs.trace_export``.
"""
from __future__ import annotations

import time
import weakref
from typing import Dict, List, Optional

import jax
import numpy as np

__all__ = ["Tracer", "NULL_TRACER", "pull"]

_Annotation = jax.profiler.TraceAnnotation
# True only while a profiler session records: the one check the disabled
# paths pay.
_profiling = _Annotation.is_enabled


# Arrays pulled so far, by id; each entry goes with its array.
_pulled: Dict[int, "weakref.ref"] = {}


def _moves_data(x) -> bool:
    """True the first time the program pulls ``x`` and no host copy of it
    exists yet: only that pull crosses from the device.  A later pull of
    the same array reads the host copy a copying backend (TPU) keeps in
    ``ArrayImpl._npy_value`` (jax 0.9), or, on a backend that lends a
    zero-copy view and keeps nothing (CPU), a view of host memory."""
    if getattr(x, "_npy_value", None) is not None:
        return False
    key = id(x)
    if key in _pulled:
        return False
    _pulled[key] = weakref.ref(x, lambda _, key=key, pop=_pulled.pop: pop(key, None))
    return True


def pull(holder, site: str, x) -> np.ndarray:
    """``np.asarray(x)``: the program's one blocking device→host transfer.

    The first pull of a ``jax.Array`` (:func:`_moves_data`) adds 1 to
    ``host_syncs`` and ``x.nbytes`` to ``d2h_bytes`` in ``holder.metrics``
    (when the holder has a registry), and, while a profiler session is
    active, runs under the annotation ``repro.pull.<site>``.  A repeated
    pull of the same array, and anything else (numpy arrays, lists,
    Python scalars), passes through ``np.asarray`` uncounted."""
    if isinstance(x, jax.Array) and _moves_data(x):
        m = getattr(holder, "metrics", None)
        if m is not None:
            m.inc("host_syncs")
            m.inc("d2h_bytes", x.nbytes)
        if _profiling():
            with _Annotation("repro.pull." + site):
                return np.asarray(x)
    return np.asarray(x)


class _NullSpan:
    """Shared do-nothing span: the whole disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def fence(self, x):
        return x

    def note(self, **kw):
        return None


_NULL_SPAN = _NullSpan()


class _AnnotatedNullSpan(_NullSpan):
    """A disabled tracer's span while a profiler session is active: the
    ``repro.<name>`` annotation only — no fence, no event."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        self._ann = _Annotation("repro." + name)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False


class _Span:
    __slots__ = ("_tracer", "name", "shard", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, shard, args: dict):
        self._tracer = tracer
        self.name = name
        self.shard = shard
        self.args = args
        self._ann = _Annotation("repro." + name) if _profiling() else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def fence(self, x):
        """Block until ``x``'s device work completes (enabled path only):
        the span's duration then covers the kernels it launched."""
        return jax.block_until_ready(x)

    def note(self, **kw):
        """Attach key/values to the span's args (visible in the trace)."""
        self.args.update(kw)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        tr = self._tracer
        tr.events.append(
            {
                "name": self.name,
                "ph": "X",
                "ts": (self._t0 - tr._epoch) * 1e6,
                "dur": (t1 - self._t0) * 1e6,
                "pid": tr.pid,
                "tid": 0 if self.shard is None else 1 + int(self.shard),
                "args": self.args,
            }
        )
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    """Span/instant recorder in Chrome trace-event form.

    ``tid`` convention: track 0 is the engine's sequencing thread (phase
    spans); track ``1 + s`` is shard ``s``'s attribution track (per-shard
    instants).  ``export(path)`` writes Perfetto-loadable JSON.
    """

    def __init__(self, enabled: bool = True, *, pid: int = 0):
        self.enabled = enabled
        self.pid = pid
        self.events: List[Dict] = []
        self._epoch = time.perf_counter()

    # -- recording -------------------------------------------------------------

    def span(self, name: str, *, shard: Optional[int] = None, **args):
        """Context manager timing one phase.  ``with tracer.span("apply")
        as sp: out = kernel(...); sp.fence(out)``.  While a profiler
        session is active the span is also the annotation
        ``repro.<name>``, enabled or not."""
        if not self.enabled:
            return _AnnotatedNullSpan(name) if _profiling() else _NULL_SPAN
        return _Span(self, name, shard, args)

    def instant(self, name: str, *, shard: Optional[int] = None, **args):
        """Zero-duration marker (per-shard attribution events)."""
        if not self.enabled:
            return
        self.events.append(
            {
                "name": name,
                "ph": "i",
                "s": "t",
                "ts": (time.perf_counter() - self._epoch) * 1e6,
                "pid": self.pid,
                "tid": 0 if shard is None else 1 + int(shard),
                "args": args,
            }
        )

    def shard_marks(self, name: str, per_shard, **extra):
        """One instant per shard with non-zero work: the per-shard
        attribution of a vmapped phase (the vmap spans all shards in one
        launch, so per-shard *time* is unobservable from the host — lane
        counts are the honest per-shard cost signal)."""
        if not self.enabled:
            return
        for s, n in enumerate(per_shard):
            if int(n):
                self.instant(name, shard=s, lanes=int(n), **extra)

    # -- lifecycle -------------------------------------------------------------

    def clear(self):
        self.events.clear()
        self._epoch = time.perf_counter()

    def export(self, path: str) -> str:
        """Write the Chrome trace-event JSON file (Perfetto-loadable)."""
        from repro.obs.trace_export import write_chrome_trace

        return write_chrome_trace(path, self)


# The disabled singleton holders fall back to when no tracer is installed.
NULL_TRACER = Tracer(enabled=False)
