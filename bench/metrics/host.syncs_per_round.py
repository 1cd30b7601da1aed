"""Blocking device-to-host pulls per round of the window: the delta of
the program's ``host_syncs`` counter (one per device array that
``repro.obs.pull`` brings to the host) over the window's rounds."""


def read(run):
    syncs = run.delta("host_syncs")
    if syncs is None or not run.rounds:
        return None
    return syncs / run.rounds
