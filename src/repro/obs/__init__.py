"""Observability layer: streaming histograms, request tracing, the event
journal and metric exporters.

A deliberately light package — numpy + stdlib, no imports from the rest of
``repro``, and jax only for the tracer's profiler annotations (imported
when a ``Tracer`` is built) — so the service tier (``repro.service``), the
retriever backends and the launchers can all depend on it without cycles,
and recording on the request hot path never touches device state.
"""
from repro.obs.events import EventJournal
from repro.obs.exporters import (JsonlMetricsWriter, histogram_to_prometheus,
                                 snapshot_to_prometheus)
from repro.obs.histogram import LogHistogram
from repro.obs.tracing import NOOP_SPAN, NOOP_TRACER, Span, Tracer

__all__ = ["EventJournal", "JsonlMetricsWriter", "LogHistogram", "NOOP_SPAN",
           "NOOP_TRACER", "Span", "Tracer", "histogram_to_prometheus",
           "snapshot_to_prometheus"]
