"""A percentile of a series the harness clocked itself (``series``,
``percentile``)."""

from benchmark import stats


def read(metric: dict, run: dict):
    series = run["harness"].get(metric["series"])
    if not series:
        return None
    return stats.percentile(series, metric["percentile"])
