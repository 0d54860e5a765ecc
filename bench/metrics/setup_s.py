"""Set-up: process start to the first timed request (imports, data
generation, load through run_tx, warm-up), host clock."""


def read(run):
    return run.setup_s
