"""95th percentile of due -> launch of E over every request sent, s.
A request never launched counts to the end of following."""
from benchmarks.chip.metric_lib import end_of_follow, percentile


def read(run):
    end = end_of_follow(run)
    return percentile([(r["launch"] if r["launch"] is not None else end) - r["due"]
                       for r in run.requests], 95)
