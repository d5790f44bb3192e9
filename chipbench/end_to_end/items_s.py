"""items_s: items (the cell's `item`: tokens here) completed in the
measured window over its wall time. All the steps, all the time: the
window opens after a fence and closes in the fenced cost read of its last
step."""


def compute(run):
    return run["items"] / run["window_s"]
