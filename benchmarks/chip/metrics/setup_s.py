"""setup_s: process start to the window's first request (JAX and TPU
start-up, compile-cache loads, warm-up, the hot pool)."""


def read(run):
    return run.setup_s
