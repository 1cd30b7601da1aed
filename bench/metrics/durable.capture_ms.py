"""Milliseconds per round in the durable commit's synchronous capture, the
``commit_capture`` span: the dirty-set take, the pull of the whole
persisted pool, the roots and the flight recorder's dump.  It sits beside
``journal_flush`` and ``manifest_commit``, not inside them."""


def read(run):
    return run.per_round_ms(("commit_capture",))
