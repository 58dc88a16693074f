"""Process start to window start: import, device, logs, warmed
programs, election, load, warm-up traffic."""

UNIT = "s"


def read(run):
    return run.setup_s
