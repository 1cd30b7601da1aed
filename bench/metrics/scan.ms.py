"""Milliseconds per round in the fenced ``scan`` span: frontier
expansion, the range-scan gather and version validation."""


def read(run):
    return run.per_round_ms(("scan",))
