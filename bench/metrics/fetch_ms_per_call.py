"""fetch_ms_per_call: time of the program's ``serving.fetch`` spans (the
wait for one shard's kernel and each session's row copied to the host)
per span."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.ms_per_span("serving.fetch") if spans else None
