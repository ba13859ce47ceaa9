"""95th percentile of due -> image on the host over every request sent, s."""
from benchmarks.chip.metric_lib import latencies, percentile


def read(run):
    return percentile(latencies(run), 95)
