"""cache_probe_s.recal: total time in ``cache.*`` spans (the memory, disk
and registry probes of a sweep's key and of its slice keys) per request of
the window."""


def read(run):
    return run.per_request("cache.")
