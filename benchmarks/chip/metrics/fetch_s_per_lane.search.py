"""fetch_s_per_lane.search: ``execute.fetch`` seconds (the device-to-host
copy of the roll-up's outputs) per engine lane of the window.  Nothing
where the run has no device trace: on a CPU backend the outputs already
live in host memory and nothing is copied."""

SPAN = "execute.fetch"


def read(run):
    if run.trace is None or not run.window_spans(SPAN):
        return None
    return run.per_lane(SPAN)
