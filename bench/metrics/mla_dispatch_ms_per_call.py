"""mla_dispatch_ms_per_call: time of the program's ``serving.dispatch``
spans (host time to enqueue one shard's latent kernel call: argument
transfers, the layer's pool slice and the call of its compiled program) per
span, in the latent cells."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.ms_per_span("serving.dispatch") if spans else None
