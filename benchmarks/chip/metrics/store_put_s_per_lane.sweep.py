"""store_put_s_per_lane.sweep: ``store.put`` seconds (memory insert and
fsynced artifact write of a lane's slice records and full-sweep result)
per engine lane of the window."""

SPAN = "store.put"


def read(run):
    return run.per_lane(SPAN) if run.window_spans(SPAN) else None
