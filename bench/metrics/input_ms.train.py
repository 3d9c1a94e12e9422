"""Host time per step that ``GSTrainer.fit`` waits for its next batch of
views (the program's ``batch`` span: drawing views, slicing cameras and
uploading the ground truth)."""


def read(run):
    spans = [t1 - t0 for name, t0, t1 in run["host_spans"] if name == "batch"]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
