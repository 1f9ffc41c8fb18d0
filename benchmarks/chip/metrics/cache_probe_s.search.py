"""cache_probe_s.search: total time in ``cache.*`` spans (memory, disk
and registry probes) per request of the window."""


def read(run):
    return run.per_request("cache.")
