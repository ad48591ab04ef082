"""A category's device time over the device's busy time, from the trace."""

from benchmark import trace_reduce


def category_share_pct(run: dict, category: str):
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    by_category = trace_reduce.category_time(trace)
    total = sum(by_category.values())
    if not total:
        return None
    return 100.0 * by_category.get(category, 0.0) / total
