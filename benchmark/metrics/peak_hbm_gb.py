"""The allocator's peak_bytes_in_use (memory_stats()) on the fullest of the
cell's devices, in GB: a counter the device keeps, and nothing else. On this
installation it leaves a running program's temporaries out; what a training
step needs while it runs is step_footprint_gb, from another source."""


def read(run):
    return run["allocator_peak_bytes"] / 1e9
