"""Replay sampling per update, in ms: the program's ``prepare`` stage (each
session's K minibatch draws and their stacking) over the updates trained."""
import _spans


def read(run):
    return _spans.ms_per_update(run, "prepare")
