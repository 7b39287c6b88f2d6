"""attend_ms_per_step: time of the program's ``serving.attend`` spans (each
``ServingTier.attend`` call: one layer's attention over the batch) per
engine step of the window, in the traced run."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    steps = len(run.window.steps)
    t = spans.ms("serving.attend") if spans else None
    return t / steps if t is not None and steps else None
