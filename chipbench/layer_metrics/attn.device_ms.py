"""attn.device_ms (layer: Kernels). Device time per step under the attention
op's scope: the leaf rows of the trace's op table (`run["trace"]["ops"]`)
whose scope starts with `flash_attention.` (`Executor`'s
`jax.named_scope("<op type>.<first output>")`), forward (both emissions of
it) and backward, over the window's steps. That is the fused kernels AND
whatever XLA runs around them for the op: layout copies, transposes,
broadcasts of the statistics, `sum(o * do)`. `kernel.flash_roofline` reads
the kernels alone; the difference is what the op costs beyond its kernels
(28.34 of 68.58 ms on gpt2-small before PR 28). Its `info` gives the split
and the time by pass. Nothing to read where no row has such a scope."""

SCOPE = "flash_attention."


def rows(run):
    ops = (run.get("trace") or {}).get("ops") or ()
    return [r for r in ops if not r["container"]
            and r["scope"].startswith(SCOPE)]


def compute(run):
    mine = rows(run)
    if not mine:
        return None
    return sum(r["ns"] for r in mine) / 1e6 / run["steps"]


def info(run):
    """ms a step: the kernels (`tpu_custom_call`s), the rest, and by pass
    (`plain`: the forward as the Program lists it; `jvp`: the forward traced
    again for differentiation; `transpose`: backward)."""
    kernels, by_pass = 0.0, {}
    for r in rows(run):
        ms = r["ns"] / 1e6 / run["steps"]
        if r["target"] == "tpu_custom_call":
            kernels += ms
        which = ("transpose" if r["transform"].startswith("transpose")
                 else r["transform"] or "plain")
        by_pass[which] = by_pass.get(which, 0.0) + ms
    return {"kernels_ms": kernels, "not_kernels_ms": compute(run) - kernels,
            "by_pass_ms": by_pass}
