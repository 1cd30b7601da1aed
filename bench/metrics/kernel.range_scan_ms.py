"""Device milliseconds per round of the Pallas range-scan kernel
(``kernels/range_scan``), found by its name in the profiler trace."""

KERNEL = "range_scan_pallas"


def read(run):
    return run.kernel_ms_per_round("kernel.range_scan_ms")
