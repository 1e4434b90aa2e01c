"""Mean host wait per step for the next batch, ``next(pipeline)`` (ms).

Read from the benchmark's own span around the call, on the host clock; it
is the input layer's time (``data/pipeline.py``) on the step's path.
"""


def read(ctx):
    waits = ctx["window"]["data_waits"]
    return 1e3 * sum(waits) / len(waits) if waits else None
