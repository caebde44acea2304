"""Peak device memory in GiB on the fullest chip, after the window: the
runtime's ``peak_bytes_in_use``.  It leaves out the scratch that the
runtime reserves for a program's temporaries (``peak_bytes_reserved``,
printed on standard error).  Work moved into held arrays shows here."""


def read(run):
    return run.memory_peak_bytes / 2**30 if run.memory_peak_bytes else None
