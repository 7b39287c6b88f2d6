"""admission_wait_ms_per_token: time of the program's
``memory.admission_wait`` spans (an admission grant waiting for headroom;
a grant given at once opens none) per token committed in the window: 0.0
when no grant waited."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    tokens = run.window.tokens
    if spans is None or not tokens:
        return None
    return (spans.ms("memory.admission_wait") or 0.0) / tokens
