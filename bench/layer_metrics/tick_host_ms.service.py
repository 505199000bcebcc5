"""Mean host time of one ingest tick (``QuantileService.ingest_batch`` of
every series), in milliseconds, from the ``tick`` spans of the window."""


def read(r):
    ticks = r.spans.get("tick", [])
    if not ticks:
        return None
    return 1e3 * sum(end - start for start, end in ticks) / len(ticks)
