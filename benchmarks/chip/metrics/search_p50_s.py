"""search_p50_s: median due-to-response latency over every search request
due in the window."""


def read(run):
    return run.quantile(0.50)
