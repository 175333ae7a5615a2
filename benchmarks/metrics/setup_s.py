"""Host clock from the top of run.py to the start of the window: imports,
generate, load, the first answer, the warm executions."""


def read(run):
    return run["setup_s"]
