"""Seconds from process start to the first timed step: imports, weights
made on the device, compiling or loading the programs from the
persistent cache, the checked steps and the warm-up step."""


def read(run):
    return run.setup_s
