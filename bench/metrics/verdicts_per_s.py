"""Verdicts completed inside the window, over the window's seconds.

Failed or degraded answers are not counted.
"""


def read(run):
    w = run.window
    return w.completed_in_window / w.seconds if w.completed_in_window else None
