"""Peak device memory after the window, ``peak_bytes_in_use`` of the
fullest chip as the device runtime reports it, in GB (1e9 bytes): what
caps the graph one chip holds."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes > 0 else None
