"""Set-up: process start to the first timed step, compilation included."""


def read(run):
    return run["setup_s"]
