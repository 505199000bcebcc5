"""Program launches on the chip per windowed query: the ``XLA Modules``
events that start inside ``query`` spans, over the number of queries."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.modules:
        return None
    queries = r.trace.spans_named("query")
    if not queries:
        return None
    return trace.launches_in(r.trace, queries) / len(queries)
