"""replica_ms_per_token: time of the program's ``serving.replicate`` spans
(committed pages shipped to the session's replica node) per token
committed in the window."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    tokens = run.window.tokens
    t = spans.ms("serving.replicate") if spans else None
    return t / tokens if t is not None and tokens else None
