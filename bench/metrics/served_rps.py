"""Requests completed inside the window over the window's length."""
import numpy as np


def read(run):
    return float(np.sum(run.done <= run.seconds)) / run.seconds
