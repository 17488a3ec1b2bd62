"""Share of the traced window in which the device idles while the host's
innermost open span is the program's ``ams.prepare`` or ``ams.place``, in
%, averaged over the cell's chips (profiler trace)."""
import _spans

PREP = ("ams.prepare", "ams.place")


def read(run):
    return _spans.share(_spans.events(run), run.chips, PREP)
