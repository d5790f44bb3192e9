"""nemotron.gmm_roofline (layer: Kernels). `kernel.gmm_roofline` for a chip's
share of the experts: the least time the chip could take for what one step's
HELD pairs need from the grouped matmul (`kernels/nemotron_grouped_matmul.py`:
two stacks, the pairs the window's `pt_moe_held_pairs_total` counted, the
published width; by `roofline.share` from `peaks.json`) over the time per
step the device spent in the `tpu_custom_call`s under a routed-FFN op's scope
(`kernel.gmm_roofline.py`'s rows: forward, the forward emitted again, the
share's recomputed forward, the backward's gmm and tgmm: all of it). That
reader's manifest entry lists the olmoe cell and its count is of three
stacks with every expert held, so this configuration brings its own (PERF.md
section 7). The kernels are handed T x k rows of which about a sixteenth are
live, in tiles of 128: the share reads low, and says how low. Never clamped.
Nothing to read where the step holds no such kernel or the registry no
held-pairs counter."""

from chipbench import roofline
from chipbench.kernels import nemotron_grouped_matmul
from chipbench.readers import load_reader


def _rows_per_step(run):
    held = load_reader("moe.held_pair_share").per_layer(
        run.get("registry"), "pt_moe_held_pairs_total")
    return sum(held.values()) / run["steps"] if held else None


def share(run):
    """(percent, bound) or None."""
    mine = load_reader("kernel.gmm_roofline").rows(run)
    pairs = _rows_per_step(run)
    if not mine or pairs is None:
        return None
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    flops, bytes_ = nemotron_grouped_matmul.flops_and_bytes(
        run["config"], run["cell"], pairs)
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine = load_reader("kernel.gmm_roofline").rows(run)
    pairs = _rows_per_step(run)
    flops, bytes_ = nemotron_grouped_matmul.flops_and_bytes(
        run["config"], run["cell"], pairs)
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "held_pairs_per_step": pairs,
            "kernels_per_step": sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
