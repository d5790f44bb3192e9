"""keye.gmm_roofline (layer: Kernels). `kernel.gmm_roofline` for a chip's share
of SwiGLU experts of width 768 with no shared expert and no dense layer: the
least time the chip could take for what one step's HELD pairs need from the
grouped matmul (`kernels/glm_grouped_matmul.py`: three stacks, the pairs the
window's `pt_moe_held_pairs_total` counted as rows, handed the one key this
configuration lacks: `first_k_dense_replace` 0, every layer routed; by
`roofline.share` from `peaks.json`) over the time per step the device spent in
the `tpu_custom_call`s under a routed-FFN op's scope (`kernel.gmm_roofline.py`'s
rows: the forward, the share's recomputed forward, the backward's gmm and
tgmm: all of it). As `glm.gmm_roofline`, `trinity.gmm_roofline` and
`lfm2.gmm_roofline`. The kernels run on a bound of 2 x T x 8 / 8 rows of which
about half are live: the share reads low, and says how low. Never clamped.
Nothing to read where the step holds no such kernel or the registry no
held-pairs counter."""

from chipbench import roofline
from chipbench.kernels import glm_grouped_matmul
from chipbench.readers import load_reader


def _rows_and_need(run):
    """(the kernels' rows, held pairs a step, (FLOPs, bytes) a step)."""
    mine = load_reader("kernel.gmm_roofline").rows(run)
    held = load_reader("moe.held_pair_share").per_layer(
        run.get("registry"), "pt_moe_held_pairs_total")
    if not mine or not held:
        return None
    pairs = sum(held.values()) / run["steps"]
    return mine, pairs, glm_grouped_matmul.flops_and_bytes(
        dict(run["config"], first_k_dense_replace=0), run["cell"], pairs)


def share(run):
    """(percent, bound) or None."""
    got = _rows_and_need(run)
    if got is None:
        return None
    mine, _, (flops, bytes_) = got
    seconds = sum(r["ns"] for r in mine) / 1e9 / run["steps"]
    return roofline.share(flops, bytes_, seconds, run["device"]["kind"])


def compute(run):
    got = share(run)
    return None if got is None else got[0]


def info(run):
    mine, pairs, (flops, bytes_) = _rows_and_need(run)
    return {"bound": share(run)[1], "flops_per_step": flops,
            "bytes_per_step": bytes_, "held_pairs_per_step": pairs,
            "kernels_per_step": sum(r["count"] for r in mine) / run["steps"],
            "kernel_ms_per_step":
            sum(r["ns"] for r in mine) / 1e6 / run["steps"]}
