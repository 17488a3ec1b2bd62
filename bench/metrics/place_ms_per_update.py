"""Placement per update, in ms: the program's ``place`` stage (stacking the
sessions' state and moving the replay batches to the device) over the
updates trained."""
import _spans


def read(run):
    return _spans.ms_per_update(run, "place")
