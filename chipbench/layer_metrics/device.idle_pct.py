"""device.idle_pct (layer: Device). 100 x (1 - busy union / traced
window) on the cell's least busy device."""


def compute(run):
    tr = run.get("trace")
    if not tr or not tr["window_s"]:
        return None
    busy = min(p["busy_ns"] for p in tr["planes"]) / 1e9
    return 100.0 * (1.0 - busy / tr["window_s"])
