"""Mean requests per render batch (the server's ``server.batch_size``)."""


def read(run):
    c = run["counters"]
    if not c["render_calls"]:
        return None
    return c["batch_size_mean"]
