"""Mean host time of one ``Dispatcher.dispatch`` call in the window, ms."""
import numpy as np


def read(run):
    d = run.spans.durations("dispatch")
    return float(np.mean(d)) * 1e3 if d else None
