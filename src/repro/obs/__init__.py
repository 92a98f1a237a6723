"""Unified observability: structured tracing + metrics registry
(DESIGN.md §13).

One import surface for every instrumented layer::

    from repro import obs

    with obs.trace("serve.decode_step", batch=4) as sp:
        ...
        sp.set(retired=2)
    obs.counter("serve_decode_steps_total").inc()

Tracing is OFF by default (``obs.enable()`` / ``--trace-out`` turns it
on; disabled spans are shared no-op singletons).  Enabled spans also open
``jax.profiler`` annotations of their name, so a profiler trace shows them
beside the device's ops.  Metrics are always on.
Export via :func:`save_chrome_trace` (Perfetto / chrome://tracing) and
:func:`save_metrics` (JSON or Prometheus text); pretty-print either with
``python -m repro.obs.report``.
"""

from repro.obs.metrics import (DEPTH_BUCKETS, LATENCY_BUCKETS,  # noqa: F401
                               REGISTRY, Counter, Gauge, Histogram,
                               Registry, counter, gauge, histogram,
                               prometheus, snapshot)
from repro.obs.metrics import save_snapshot as save_metrics  # noqa: F401
from repro.obs.tracing import (NOOP_SPAN, Span, async_begin,  # noqa: F401
                               async_end, chrome_trace, clear, disable,
                               enable, enabled, event, monotonic,
                               monotonic_ns, records, save_chrome_trace,
                               spans, trace)
