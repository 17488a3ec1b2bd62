"""Replay batch bytes sent to the device per update, in MB: the ``nbytes``
the program's ``place`` stage counts (frames and labels) over the window,
over the updates trained."""


def read(run):
    if run.timing is None:
        return None
    sent = [e["nbytes"] for (stage, _key), e in run.timing.items()
            if stage == "place"]
    n = sum(g["width"] for g in run.grants)
    return sum(sent) / n / 1e6 if sent and n else None
