"""Share of the prefix cache's block lookups in the window that hit
(``BlockPool.prefix_hits / prefix_queries``, window deltas)."""


def read(ctx):
    hits, queries = ctx["prefix"]
    return 100.0 * hits / queries if queries else None
