"""From process start to the first timed request: imports, device,
weights from the seed, request pool, compile and warm-up (host clock)."""


def read(run):
    return run.setup_s
