"""kernel.pallas_share_pct (layer: Kernels). Share of the first device's
busy time spent in compiled Pallas / Mosaic kernels (`tpu_custom_call`s;
the rule that finds them is `xplane.is_custom_call`). 0 where the step
holds none: the dispatch took the XLA formulation."""


def compute(run):
    tr = run.get("trace")
    if not tr or not tr["planes"][0]["busy_ns"]:
        return None
    p = tr["planes"][0]
    return 100.0 * p["custom_call_ns"] / p["busy_ns"]
