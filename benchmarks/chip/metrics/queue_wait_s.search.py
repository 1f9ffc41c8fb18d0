"""queue_wait_s.search: mean ``request.queued`` span (admission to the
batching window's drain) per request of the window."""


def read(run):
    return run.per_request("request.queued")
