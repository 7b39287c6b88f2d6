"""device_idle.dispatch: share of the traced window, in %, in which no
operation ran on a device while the innermost program span was
``serving.dispatch``, averaged over the devices in use."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.idle_share("serving.dispatch") if spans else None
