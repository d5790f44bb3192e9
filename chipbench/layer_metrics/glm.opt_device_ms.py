"""glm.opt_device_ms: `opt.device_ms` on the glm-4.7-flash cells, under a name of its own. That
reader's manifest entry lists the cells of the configurations that were
there, and a `model_config` PR may not edit an entry that is there (PERF.md
section 7): this file only loads `opt.device_ms.py` by path and returns what its
`compute(run)` returns, so the shared code (see that file's docstring for
what is measured) is seen on this configuration too. A later `benchmark` PR
that drops the `workloads` list of `opt.device_ms` retires this file."""

from chipbench.readers import load_reader

WRAPS = "opt.device_ms"


def compute(run):
    return load_reader(WRAPS).compute(run)
