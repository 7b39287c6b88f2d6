"""restore_ms_per_page: time of the program's ``kvcache.restore`` spans (one
page brought back into the HBM pool, the evictions it forces included)
per span."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.ms_per_span("kvcache.restore") if spans else None
