"""Round-engine telemetry: phase tracing, per-shard metrics, exporters,
and the semantic flight recorder + linearizability witness.

Cooperating layers, all host-side (nothing here ever enters a jitted
program — the overhead guarantee the spy tests pin):

  * :mod:`repro.obs.tracer` — ``Tracer``: span timers around every phase of
    the ``core/rounds.py`` pipeline (``scan → search/combine → apply →
    retry → rebalance``, plus occ sub-rounds, structural waves, router
    pack/stitch, journal flushes, manifest commits, serve ticks).  Fences
    with ``jax.block_until_ready`` ONLY when enabled; disabled it is a
    single attribute check returning a shared no-op span.  While a
    ``jax.profiler`` session runs, every span is also a
    ``repro.<name>`` profiler annotation.  ``pull`` is the engine's one
    device→host transfer: it counts ``host_syncs`` / ``d2h_bytes`` in the
    holder's registry and annotates ``repro.pull.<site>``.
  * :mod:`repro.obs.metrics` — ``MetricsRegistry``: counters / gauges /
    histograms with optional per-shard attribution, one queryable
    ``snapshot()`` absorbing the engine's scattered counter surfaces
    (``_rounds`` / ``_scans`` / ``_scan_retries`` / ``DurableStats`` /
    device ``TreeStats``).
  * :mod:`repro.obs.recorder` — ``Recorder``: always-on bounded ring of
    semantic per-round audit records (lane ops/keys/results, elimination
    pairings, occ sub-round structure, scan validation outcomes, forest
    transitions).  Disabled it follows the tracer's exact no-op contract.
  * :mod:`repro.obs.witness` — replays a recorded history through the
    sequential ``DictOracle`` and verifies the engine's chosen
    linearization is a legal sequential history (CLI:
    ``python -m repro.obs.witness audit.jsonl``).
  * :mod:`repro.obs.trace_export` / :mod:`repro.obs.report` /
    :mod:`repro.obs.hlo_audit` — Chrome trace-event JSON (Perfetto-
    loadable), the phase/shard breakdown + forensics CLI (``--json`` for
    machines), and the reusable HLO sort/gather audit.

See ``src/repro/obs/README.md`` for the contract and overhead guarantees.
"""
from repro.obs.metrics import (
    MetricsRegistry,
    RegistryBackedCounters,
    engine_collector,
)
from repro.obs.recorder import NULL_RECORDER, Recorder
from repro.obs.tracer import NULL_TRACER, Tracer, pull

__all__ = [
    "MetricsRegistry",
    "RegistryBackedCounters",
    "Tracer",
    "NULL_TRACER",
    "pull",
    "Recorder",
    "NULL_RECORDER",
    "engine_collector",
]
