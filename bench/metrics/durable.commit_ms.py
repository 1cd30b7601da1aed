"""Milliseconds per round in the durable commit: the ``journal_flush``
(segment or snapshot write and fsync) and ``manifest_commit`` spans."""


def read(run):
    return run.per_round_ms(("journal_flush", "manifest_commit"))
