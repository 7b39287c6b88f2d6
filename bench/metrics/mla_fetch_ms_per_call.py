"""mla_fetch_ms_per_call: time of the program's ``serving.fetch`` spans (the
wait for one shard's latent kernel and each session's ``[q_heads,
value_dim]`` row copied to the host) per span, in the latent cells."""
from bench import program_spans


def read(run):
    spans = program_spans.for_run(run)
    return spans.ms_per_span("serving.fetch") if spans else None
