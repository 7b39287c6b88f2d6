"""setup_s: seconds from the process's start to the first timed operation
(loading, data, warm-up and any compilation), on the host clock."""


def read(run):
    return run.setup_s
