"""Observability cost + span-census benchmark for :mod:`repro.obs`.

Two questions, both asserted in CI's bench.json:

  ``obs/spans_per_request``   the deterministic span census: a cache-cold
                              stream of N unique requests through the
                              admission frontend must record *exactly*
                              ``8N + 3 + 4G`` request-path spans over G
                              vmap groups (per request: request,
                              request.queued, request.batched, cache.mem,
                              request.engine; per unique miss:
                              engine.extract, its one extract.mask and
                              its store.put; per fused batch: engine.pass,
                              engine.plan, engine.execute; per group:
                              execute.pack/wait/fetch/unpack).  Host spans
                              (``host.*``, ``jax.*``) and the scheduler's
                              ``frontend.*`` depend on the interpreter,
                              the compile cache and timing, and are not
                              counted.  ``exact=True`` is the CI gate, so a
                              silently added or dropped instrumentation
                              point fails the build;
  ``obs/trace_overhead_pct``  tracing cost as a fraction of the p50
                              request latency.  E2e wall-clock diffs are
                              noise-dominated at this scale, so the
                              overhead is microbenchmark-derived: measured
                              per-span record cost x spans-per-request /
                              the measured p50 request latency.  CI
                              asserts ``on_pct <= 5`` (full sampling) and
                              ``off_pct <= 1`` (tracing disabled — the
                              noop-span fast path).

With ``OBS_TRACE_OUT=PATH`` in the environment the census run's spans are
also exported as a Chrome-trace JSON (CI uploads it as an artifact, so
every build carries a Perfetto-loadable serving timeline).
"""

from __future__ import annotations

import os
import time

from repro.core import calibrated_tech_for_reference, engine
from repro.core.shardspec import spec_variants
from repro.obs import configure, tracer, write_chrome_trace
from repro.service import ServiceFrontend, SynthesisRequest, SynthesisService

N_UNIQUE = 4           # distinct specs in the cache-cold stream
GRID_RESOLUTION = 3
SPAN_ITERS = 20_000    # span-cost microbenchmark repetitions

#: Span name prefixes outside the census (runtime- and timing-dependent).
UNCOUNTED = ("host.", "jax.", "frontend.")


def _serve_stream(uniques, tech):
    """One cache-cold pass of the stream through a deterministic frontend
    (no scheduler thread: ``run_pending`` drains one batch per call, and
    ``max_batch >= N`` makes it exactly one fused pass)."""
    svc = SynthesisService(tech=tech, resolution=GRID_RESOLUTION)
    front = ServiceFrontend(svc, max_batch=2 * len(uniques), start=False)
    tickets = [front.submit(SynthesisRequest(spec=s)) for s in uniques]
    while front.run_pending():
        pass
    responses = [t.result(timeout=600) for t in tickets]
    front.close()
    return responses


def _span_cost_s() -> float:
    """Per-span create+finish cost on the current tracer posture."""
    t0 = time.perf_counter()
    for _ in range(SPAN_ITERS):
        with tracer.span("bench.span"):
            pass
    return (time.perf_counter() - t0) / SPAN_ITERS


def run() -> list[tuple]:
    tech = calibrated_tech_for_reference()
    uniques = spec_variants(N_UNIQUE, seed=0)

    # Tracing OFF: the baseline p50 request latency (first pass warms the
    # jit caches so the measured pass times serving, not XLA compiles) and
    # the noop-span fast-path cost.
    configure(enabled=False)
    tracer.clear()
    _serve_stream(uniques, tech)
    responses = _serve_stream(uniques, tech)
    lats = sorted(r.latency_s for r in responses)
    p50_s = lats[len(lats) // 2]
    cost_off_s = _span_cost_s()

    # Tracing ON at full sampling: the deterministic span census.
    configure(enabled=True, sample=1.0)
    tracer.clear()
    _serve_stream(uniques, tech)
    spans = [s for s in tracer.drain() if not s.name.startswith(UNCOUNTED)]
    n_groups = len(engine.plan(uniques, tech).groups)
    expected = 8 * N_UNIQUE + 3 + 4 * n_groups
    n_spans = len(spans)
    per_request = n_spans / N_UNIQUE

    out = os.environ.get("OBS_TRACE_OUT")
    if out:
        write_chrome_trace(spans, out)

    # Per-span record cost under a live trace root.
    with tracer.start_trace("bench.root"):
        cost_on_s = _span_cost_s()
    tracer.clear()
    configure(enabled=False)

    on_pct = 100.0 * per_request * cost_on_s / p50_s
    off_pct = 100.0 * per_request * cost_off_s / p50_s

    return [
        ("obs/spans_per_request", cost_on_s * 1e6,
         f"per_request={per_request:.1f};spans={n_spans};"
         f"expected={expected};exact={n_spans == expected};"
         f"requests={N_UNIQUE}"),
        ("obs/trace_overhead_pct", cost_on_s * 1e6,
         f"on_pct={on_pct:.4f};off_pct={off_pct:.4f};"
         f"p50_ms={p50_s * 1e3:.2f};span_ns_on={cost_on_s * 1e9:.0f};"
         f"span_ns_off={cost_off_s * 1e9:.0f}"),
    ]
