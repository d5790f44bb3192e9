"""nemotron.moe_device_ms: `moe.device_ms` on the nemotron-3-nano-30b-a3b cells, under a name of its
own. That reader's manifest entry lists the olmoe cell, and a `model_config`
PR may not edit an entry that is there (PERF.md section 7): this file loads
`moe.device_ms.py` by path and returns what its `compute(run)` returns (see
that file's docstring for what is measured). Its `info` is that reader's
with one inner scope more, `shared`: the shared expert's two matmuls, which
the op this configuration builds runs beside the routed ones. A later
`benchmark` PR that drops the `workloads` list of `moe.device_ms` retires
this file."""

from chipbench.readers import load_reader

WRAPS = "moe.device_ms"
INNER = ("route", "dispatch", "experts", "combine", "shared")


def compute(run):
    return load_reader(WRAPS).compute(run)


def info(run):
    wrapped = load_reader(WRAPS)
    by_scope, kernels = {}, 0.0
    for r in wrapped.rows(run):
        inner = next((p for p in r["op_name"].split("/") if p in INNER),
                     "other")
        ms = r["ns"] / 1e6 / run["steps"]
        by_scope[inner] = by_scope.get(inner, 0.0) + ms
        kernels += ms if wrapped.is_kernel(r) else 0.0
    return {"by_inner_scope_ms": by_scope, "kernels_ms": kernels,
            "by_pass_ms": wrapped.info(run)["by_pass_ms"]}
