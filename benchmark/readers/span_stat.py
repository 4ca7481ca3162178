"""The median of one of the runner's own series (host clock)."""
from benchmark.harness import stats


def read(rin, span):
    values = rin.spans.get(span)
    if not values:
        return None
    return stats.median(values)
