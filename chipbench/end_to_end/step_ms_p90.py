"""step_ms_p90: 90th percentile, over ALL the window's sync intervals, of
(host time between two consecutive fenced cost reads) / (steps in the
interval), in ms. Nearest rank, nothing trimmed. A run with fewer than
100 intervals fails: a 90th percentile needs ten samples beyond it."""

from chipbench import stats


def compute(run):
    return 1e3 * stats.percentile(
        run["intervals_s"], 90,
        run.get("min_intervals", stats.MIN_INTERVALS))
