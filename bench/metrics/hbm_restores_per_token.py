"""hbm_restores_per_token: pages restored into HBM pools during the window
(``PagedKVCache.stats["fetches"]`` summed over nodes) per token committed."""


def read(run):
    w = run.window
    return w.fetches / w.tokens if w.tokens else None
