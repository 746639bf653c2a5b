"""Most KV pool pages held at once (the engine's peak_pages_held, since
the engine was built) as a share of the pool."""


def read(run):
    return 100.0 * run.counters_close["peak_pages_held"] / run.pool_pages
