"""Node-image bytes made durable per operation in the window
(``DurableStats.flush_bytes`` over operations)."""


def read(run):
    flushed = run.delta("flush_bytes")
    if flushed is None or not run.ops:
        return None
    return flushed / run.ops
