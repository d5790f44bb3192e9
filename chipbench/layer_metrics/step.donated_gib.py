"""step.donated_gib (layer: Executor step). GiB of persistable buffers the
live executors' compiled step programs donate: the `pt_executor_donated_bytes`
gauge of the program's metrics registry at the window's close
(`run["registry"]`; `Executor.donation_stats` summed over the compiled
programs that rebind persistables). What a step overwrites in place instead
of holding twice: it should equal the parameters plus the optimizer's state.
Nothing to read where the program registers no such gauge."""


def compute(run):
    donated = (run.get("registry") or {}).get("pt_executor_donated_bytes")
    return None if donated is None else donated / 2.0**30
