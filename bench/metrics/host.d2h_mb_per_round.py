"""Device-to-host megabytes (10^6 bytes) per round of the window: the
delta of the program's ``d2h_bytes`` counter (the bytes of every device
array that ``repro.obs.pull`` brings to the host) over the window's rounds."""


def read(run):
    moved = run.delta("d2h_bytes")
    if moved is None or not run.rounds:
        return None
    return moved / 1e6 / run.rounds
