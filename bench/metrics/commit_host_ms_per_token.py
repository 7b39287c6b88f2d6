"""commit_host_ms_per_token: host time of the ``serve.commit`` spans
(``ServingTier.decode``: page write, replica sync, admission) per token
committed in the window."""


def read(run):
    t = run.spans.seconds.get("serve.commit")
    return 1e3 * t / run.window.tokens if t and run.window.tokens else None
