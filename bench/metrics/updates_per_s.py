"""Session updates completed per second: updates of the grants begun in the
window over the time from the window's start to the end of the last of
them (whole grants: all the work over all the time)."""


def read(run):
    done = [u for u in run.window_updates() if u["ok"]]
    return len(done) / run.busy_end() if done else None
