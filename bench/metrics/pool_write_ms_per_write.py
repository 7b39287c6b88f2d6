"""pool_write_ms_per_write: time of the program's ``kvcache.pool_write``
spans (host side of one page slab written into the HBM pool: its transfer
and the pool update) per span."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.ms_per_span("kvcache.pool_write") if spans else None
