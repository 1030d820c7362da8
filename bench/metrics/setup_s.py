"""Set-up time: process start to the first timed call.

Imports, the device check, the service-time table, loading (or, in a
checkout's first run, compiling) the cell's programs and one warm-up call
per shape.
"""


def read(run):
    return run.setup_s
