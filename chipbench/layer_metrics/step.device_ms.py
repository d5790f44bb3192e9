"""step.device_ms (layer: Executor step). Device time per step: the union
of the op intervals on the first device's `XLA Ops` line inside the
traced window, over the steps in it."""


def compute(run):
    tr = run.get("trace")
    if not tr:
        return None
    return tr["planes"][0]["busy_ns"] / 1e6 / run["steps"]
