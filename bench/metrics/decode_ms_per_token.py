"""decode_ms_per_token: time of the program's ``serving.decode`` spans (the
commit path of ``ServingTier.decode``) per token committed in the window."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    tokens = run.window.tokens
    t = spans.ms("serving.decode") if spans else None
    return t / tokens if t is not None and tokens else None
