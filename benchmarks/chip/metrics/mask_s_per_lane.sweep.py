"""mask_s_per_lane.sweep: ``extract.mask`` seconds (the Pareto survivor
masks of a lane's frontier and slice records, on the host or sharded over
the chips) per engine lane of the window."""

SPAN = "extract.mask"


def read(run):
    return run.per_lane(SPAN) if run.window_spans(SPAN) else None
