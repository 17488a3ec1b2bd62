"""Host preparation per update, in ms: the `train_many` calls' wall time
minus the train, selection and encode stages the program times inside
them, over the updates trained."""
import _stages


def read(run):
    timed = _stages.seconds(run, *_stages.TIMED)
    n = sum(g["width"] for g in run.grants)
    if timed is None or not n:
        return None
    return (sum(g["train_s"] for g in run.grants) - timed) / n * 1000
