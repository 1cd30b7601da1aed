"""Milliseconds per round in the fenced ``search_combine`` span: the
descent, the leaf probe and publishing elimination's combine."""


def read(run):
    return run.per_round_ms(("search_combine",))
