"""Device milliseconds per round of the Pallas descent+probe kernel
(``kernels/tree_descend``), found by its name in the profiler trace."""

KERNEL = "descend_probe_pallas"


def read(run):
    return run.kernel_ms_per_round("kernel.descend_ms")
