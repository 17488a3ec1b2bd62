"""Commit per update, in ms: the program's ``commit`` stage (the losses
pulled, the stacked state unstacked and adopted per session) over the
updates trained."""
import _spans


def read(run):
    return _spans.ms_per_update(run, "commit")
