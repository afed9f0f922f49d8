"""Unified telemetry: device-side wire/drop/shadow counters
(:mod:`counters`), the train step's named scopes (:mod:`scopes`), host
spans on the profiler's clock and the CLIs' profiler window (:mod:`trace`),
pluggable metrics sinks (:mod:`sink`), the modeled-vs-measured StepStats
record (:mod:`stats`), and the resilience layer's incident-event vocabulary
(:mod:`events`).

Import discipline: :mod:`counters` and :mod:`scopes` depend only on jax,
:mod:`trace` (which imports jax only when a span or window is used) and
:mod:`sink` only on the stdlib (+numpy), so ``repro.core`` may import them
without cycles; :mod:`stats` pulls ``repro.launch.roofline`` lazily.
"""
from repro.obs import events  # noqa: F401
from repro.obs import trace  # noqa: F401
from repro.obs.counters import ObsCounters  # noqa: F401
from repro.obs.sink import (CsvSink, JsonlSink, MemorySink,  # noqa: F401
                            MetricsSink, MultiSink, jsonl_records)
from repro.obs.stats import StepStats, modeled_collective_bytes  # noqa: F401
