"""gc_share.sweep: seconds of ``host.gc`` spans (garbage collections of
1 ms or more) inside the window, over the window, in percent.  Nothing
where the program records no collections."""

import importlib.util

SPAN = "host.gc"


def read(run):
    if importlib.util.find_spec("repro.obs.host") is None:
        return None
    inside = sum(max(0.0, min(s.end_s, run.t_end) - max(s.start_s, run.t0))
                 for s in run.spans if s.name == SPAN and s.end_s is not None)
    return 100.0 * inside / (run.t_end - run.t0)
