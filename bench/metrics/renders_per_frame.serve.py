"""Full-frame renders per served frame (the server's ``render_rows`` over
tile rows times frames completed): 1.0 when the tile cache serves nothing."""


def read(run):
    c = run["counters"]
    if not c["completed"]:
        return None
    return c["render_rows"] / (c["tiles_y"] * c["completed"])
