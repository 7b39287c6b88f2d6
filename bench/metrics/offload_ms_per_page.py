"""offload_ms_per_page: time of the program's ``kvcache.offload`` spans (one
evicted page: its copy out of the pool and the host tier's ``put``) per
span."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.ms_per_span("kvcache.offload") if spans else None
