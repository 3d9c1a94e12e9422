"""Mean time a request waits in the server's micro-batcher: the program's
``queue`` spans, from the request's due time to the dispatch of the batch
that takes it."""
from spans import durations_s


def read(run):
    waits = durations_s(run, "queue")
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3
