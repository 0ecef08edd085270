"""Percentile and spread arithmetic, numpy only."""
import numpy as np


def percentile(samples, q):
    """The q-th percentile (0..100, linear interpolation) of `samples`,
    or None when there are none: a metric with nothing to read is left
    out, never reported as 0."""
    if len(samples) == 0:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), q))


def median(samples):
    return percentile(samples, 50)


def spread(values):
    """Distance between the quartiles over the median: how the driver
    reads the run-to-run spread of one metric in one cell."""
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)
