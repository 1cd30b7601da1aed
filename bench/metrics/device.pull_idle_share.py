"""Percent of the traced window in which the device sat idle while the
host was inside one of the program's pulls: the idle seconds that
``bench/idle_spans.py`` lays under ``repro.pull.*`` annotations, over the
window.  None where no idle time lies under any of the program's
annotations, as in a program that does not make them."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    if run.trace is None:
        return None
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import idle_spans

    red = idle_spans.for_run(os.path.dirname(BENCH), run.trace)
    if red is None or not red["window_s"]:
        return None
    spans = dict(red["idle_by_span"])
    if not any(n.startswith(idle_spans.PREFIX) for n in spans):
        return None
    pulls = sum(s for n, s in spans.items() if n.startswith(idle_spans.PULL))
    return 100.0 * pulls / red["window_s"]
