"""incremental_s_per_sweep.recal: mean ``service.sweep`` span per sweep of
the window answered by incremental re-synthesis: the roll-up of the
sublattice of the missing axis values, their slice records and the merge
with the cached slices.  The probes of the slice keys come before the span
(``cache_probe_s.recal``)."""

SPAN = "service.sweep"


def read(run):
    spans = run.window_spans(SPAN)
    return sum(s.duration_s for s in spans) / len(spans) if spans else None
