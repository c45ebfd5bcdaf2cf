"""Set-up: from the process's start to the window's (imports, the chip's
start-up, plans and warm-up with their compiles or cache loads)."""


def read(run):
    return run["setup_s"]
