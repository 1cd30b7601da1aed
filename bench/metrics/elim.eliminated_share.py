"""Percent of the window's update lanes (inserts and deletes) that
publishing elimination answered without a write (``TreeStats.eliminated``)."""


def read(run):
    updates = run.lanes.get("insert", 0) + run.lanes.get("delete", 0)
    eliminated = run.delta("eliminated")
    if not updates or eliminated is None:
        return None
    return 100.0 * eliminated / updates
