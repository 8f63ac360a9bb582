"""Admission decisions (plan + commit, rejections included) completed in the
window, over the window's length on the host clock."""


def read(run):
    return len(run.decisions) / run.window_s
