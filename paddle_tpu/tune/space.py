"""Per-kernel-family rules: legality, candidates, the default, the pick.

A family's tile, block or fused-or-scan choice is a function of its
shapes and dtype, and this module is the one place it is written. Per
family: a legality predicate, the candidate generator the sweep tool
walks (tune/harness.py), and the default rule. The kernels call
`pick(family, params, dtype)`: the config forced through
tune/overrides.py where one is and it is legal at the shape, else the
default. Nothing else overrules a default: no table, file, environment
variable or flag.

Legality has two ingredients per family:
- Mosaic tile rules: the last-two-dims (8k, 128k)-or-full block-shape
  rule (see `bahdanau_blk_legal`), lane
  alignment, and divide-the-array constraints;
- the VMEM-budget working-set models lifted from the kernels (sized
  against the 15 MiB scoped budget in ops/pallas_kernels._VMEM_BUDGET,
  which reproduces every measured compile overflow — see its comment).

Anything in `ops/` is imported lazily: the kernels import this module,
and it imports them back for their VMEM constants and case runners.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, Optional

from . import overrides

Params = Dict[str, Any]
Config = Dict[str, Any]


def _vmem_budget() -> int:
    from ..ops.pallas_kernels import _VMEM_BUDGET

    return _VMEM_BUDGET


def pad_s(s: int) -> int:
    """Source-length padding shared with bahdanau_kernels._pad_s: the
    attention kernels run over S padded to a sublane-tileable multiple
    of 16."""
    return ((s + 15) // 16) * 16


def _dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "int8": jnp.int8}[name]


# io dtypes a sweep can name; a kernel that only knows its itemsize
# names its dtype through ITEMSIZE_DTYPE
DTYPES = ("bfloat16", "float32", "int8")
ITEMSIZE_DTYPE = {1: "int8", 2: "bfloat16", 4: "float32", 8: "float64"}


def _itemsize(dtype_name: str) -> int:
    import numpy as np

    return 2 if dtype_name == "bfloat16" else np.dtype(dtype_name).itemsize


# ------------------------------------------------------------- bahdanau --
def bahdanau_blk_legal(b: int, B: int, Sp: int, A: int, C: int,
                       itemsize: int) -> bool:
    """Batch-tile legality shared by ALL the attention kernels (fwd,
    bwd-step, phase-2 share one eligibility so a config never runs fused
    forward and then fails to tile the backward). Divisibility: b must
    divide B, and be a sublane multiple (8) unless it spans the whole
    batch dim — the Mosaic last-two-dims (8k, 128k)-or-full rule (B=4
    and B=2 verified lowering on v5e hardware, round 5). The VMEM term
    models the largest working set in the family (phase-2's):
    double-buffered ep/enc io tiles, the once-written io-dtype dep
    output block, and five f32 [blk, Sp, A] working arrays."""
    if b <= 0 or B <= 0 or B % b:
        return False
    if b % 8 and b != B:
        return False
    return ((2 * Sp * (A + C) + Sp * A) * b * itemsize
            + 5 * b * Sp * A * 4) <= _vmem_budget()


def _bahdanau_legal(params: Params, config: Config) -> bool:
    return bahdanau_blk_legal(
        int(config.get("bblk", 0)), params["B"], params["Sp"], params["A"],
        params["C"], _itemsize(params["dtype"]))


def bahdanau_candidates(params: Params) -> List[Config]:
    return [{"bblk": b} for b in range(1, params["B"] + 1)
            if _bahdanau_legal(params, {"bblk": b})]


def bahdanau_default(params: Params) -> Optional[Config]:
    """8 measured best on v5e at the NMT shapes (256k tok/s vs 217k at
    16/32, bs256 sweep: larger tiles triple the f32 temporaries and
    spill); 4 and 2 are for SMALL batches only (a sub-8 tile is a legal
    Mosaic block shape only when it spans the whole batch dim; B=4 and
    B=2 verified lowering and matching on v5e hardware, round 5). None:
    no legal tile, the fused decoder is off at this shape."""
    for b in (8, 4, 2):
        if _bahdanau_legal(params, {"bblk": b}):
            return {"bblk": b}
    return None


def _bahdanau_case(params: Params, dtype: str) -> "Case":
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..ops import bahdanau_kernels as bk

    B, Sp, A, C = params["B"], params["Sp"], params["A"], params["C"]
    rng = np.random.RandomState(0)
    dt = _dtype_of(dtype)
    ep = jnp.asarray(rng.randn(B, Sp, A) * 0.3, dt)
    enc = jnp.asarray(rng.randn(B, Sp, C) * 0.3, dt)
    dp = jnp.asarray(rng.randn(B, A) * 0.3, dt)
    v = jnp.asarray(rng.randn(A) / np.sqrt(A), dt)
    maskf = jnp.ones((B, Sp), jnp.float32)
    interpret = jax.default_backend() != "tpu"
    args = (ep, enc, dp, v, maskf)

    def make(config: Config) -> Callable[[], Any]:
        from . import overrides

        def f(ep, enc, dp, v, maskf):
            return bk._attn_fwd(ep, enc, dp, v, maskf, interpret)[0]

        jf = jax.jit(f)
        with overrides.forcing("bahdanau_attention", config):
            jf(*args)  # trace+compile while the forced tile is active
        return lambda: jf(*args)

    def ref():
        epf, encf = np.asarray(ep, np.float32), np.asarray(enc, np.float32)
        dpf, vf = np.asarray(dp, np.float32), np.asarray(v, np.float32)
        t = np.tanh(epf + dpf[:, None, :])
        scores = (t * vf[None, None, :]).sum(-1)
        scores = np.where(np.asarray(maskf) > 0, scores, -1e9)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        alpha = e / e.sum(-1, keepdims=True)
        return [np.einsum("bs,bsc->bc", alpha, encf)]

    return Case("bahdanau_attention", make, ref,
                tol=2e-2 if dtype == "bfloat16" else 2e-5)


# ---------------------------------------------------------------- flash --
FLASH_BLOCK_GRID = (128, 256, 384, 512, 640, 768, 1024, 1536, 2048)


def flash_block_legal(bq: int, bk: int, Tq: int, Tk: int) -> bool:
    """The TPU flash kernels require blocks to DIVIDE the sequence and
    be lane-aligned (128)."""
    return (bq > 0 and bk > 0 and bq % 128 == 0 and bk % 128 == 0
            and Tq % bq == 0 and Tk % bk == 0)


def _flash_legal(params: Params, config: Config) -> bool:
    return flash_block_legal(int(config.get("block_q", 0)),
                             int(config.get("block_k", 0)),
                             params["Tq"], params["Tk"])


def flash_candidates(params: Params) -> List[Config]:
    Tq, Tk = params["Tq"], params["Tk"]
    qs = [b for b in FLASH_BLOCK_GRID if flash_block_legal(b, 128, Tq, 128)]
    ks = [b for b in FLASH_BLOCK_GRID if flash_block_legal(128, b, 128, Tk)]
    return [{"block_q": q, "block_k": k} for q in qs for k in ks]


def flash_default(params: Params) -> Optional[Config]:
    """512-row q/k blocks up to T=4096, 1024 from 8192, rounded down to
    the largest 128-multiple divisor (e.g. T=1280 -> 256). None: a
    sequence that is not 128-aligned has no legal block."""
    def blk(T):
        if T % 128:
            return 0
        b = min(T, 512 if T < 8192 else 1024)
        while T % b:
            b -= 128
        return b

    bq, bk = blk(params["Tq"]), blk(params["Tk"])
    if not bq or not bk:
        return None
    return {"block_q": bq, "block_k": bk}


def _flash_case(params: Params, dtype: str) -> "Case":
    import numpy as np

    import jax.numpy as jnp

    from ..ops import flash_ops

    B = params.get("B", 4)
    H = params.get("H", 8)
    D = params.get("D", 128)
    Tq, Tk = params["Tq"], params["Tk"]
    rng = np.random.RandomState(0)
    dt = _dtype_of(dtype)
    q = jnp.asarray(rng.randn(B, Tq, H, D) * 0.1, dt)
    k = jnp.asarray(rng.randn(B, Tk, H, D) * 0.1, dt)
    v = jnp.asarray(rng.randn(B, Tk, H, D) * 0.1, dt)
    args = (q, k, v)

    def make(config: Config) -> Callable[[], Any]:
        import jax

        from . import overrides

        jf = jax.jit(lambda q, k, v: flash_ops._flash_kernel(
            q, k, v, causal=False))
        with overrides.forcing("flash_attention", config):
            jf(*args)
        return lambda: jf(*args)

    def ref():
        return [np.asarray(
            flash_ops.scaled_dot_product_attention(q, k, v, causal=False),
            np.float32)]

    return Case("flash_attention", make, ref,
                tol=5e-2 if dtype == "bfloat16" else 2e-4)


# ------------------------------------------------------------- RNN cells --
def _rnn_hard_ok(kind: str, B: int, H: int, itemsize: int) -> bool:
    """Hard (non-empirical) fused-RNN legality: tile alignment + the
    backward-kernel VMEM model from ops/pallas_kernels. The gate forms
    and the backend are lstm_supported's / gru_supported's to check."""
    from ..ops import pallas_kernels as pk

    if not (B >= 8 and B % 8 == 0 and H % 128 == 0):
        return False
    g = 4 if kind == "lstm" else 3
    dw_max = (pk._LSTM_FUSED_DW_MAX_H if kind == "lstm"
              else pk._GRU_FUSED_DW_MAX_H)
    return pk._bwd_vmem_bytes(B, H, g, itemsize, dw_max) <= pk._VMEM_BUDGET


def _rnn_legal(kind: str):
    def legal(params: Params, config: Config) -> bool:
        if "fused" not in config:
            return False
        return not config["fused"] or _rnn_hard_ok(
            kind, params["B"], params["H"], _itemsize(params["dtype"]))

    return legal


def _rnn_candidates(kind: str):
    legal = _rnn_legal(kind)
    return lambda params: [c for c in ({"fused": True}, {"fused": False})
                           if legal(params, c)]


def _rnn_default(kind: str):
    def default(params: Params) -> Config:
        B, H = params["B"], params["H"]
        if not _rnn_hard_ok(kind, B, H, _itemsize(params["dtype"])):
            return {"fused": False}
        # The measured windows. LSTM's upper end was read on the v5e in
        # PR 48 (forward + backward of one layer, bf16, T 100, ten
        # alternating pairs, kernel over lax.scan: PERF.md section 6):
        # 1.22x at B 128 H 1024, 1.17x at B 128 H 1152, 1.73x at B 32
        # H 1280, 1.81x at B 32 H 1152; at B 128 H 1280 the VMEM model
        # above decides, not the window. The rest is from before this
        # chip (rnn_kernel_microbench, rounds <= 5, the old link; record
        # in git history). LSTM: 1.02x at H 512, 1.45x at 768; H 256
        # loses (0.86x): the per-step matmul is too small to amortize
        # the kernel's fixed work. GRU: 1.18x at H 128, 1.06x at 256,
        # 1.72x at 512 (the NMT config), 1.70x at 640, 1.24x at 768,
        # 1.61x at 1024, 1.88x at 1280; H 384 alone dips to 0.86x
        # (3H = 1152 tiles badly against the 512-lane MXU pass).
        if kind == "lstm":
            return {"fused": 384 <= H <= 1280}
        return {"fused": 128 <= H <= 1280 and H != 384}

    return default


# ----------------------------------------------------------- quant matmul --
# Output-tile grids for the int8 GEMM: block_m walks the int8 sublane
# tile (32 — Mosaic's (32, 128) minimum int8 tile, pallas guide), block_n
# the 128 lane dim.
QUANT_BLOCK_M = (32, 64, 128, 256, 512)
QUANT_BLOCK_N = (128, 256, 512, 1024)


def quant_matmul_legal(bm: int, bn: int, M: int, K: int, N: int) -> bool:
    """Tile legality of the int8×int8→int32 kernel
    (ops/quant_kernels._quant_matmul_pallas): blocks divide the output,
    respect int8's (32, 128) minimum tile (unless spanning the whole
    dim), and the working set — double-buffered int8 x/w panels plus
    the int32 accumulator block — fits VMEM."""
    if bm <= 0 or bn <= 0 or M % bm or N % bn:
        return False
    if bm % 32 and bm != M:
        return False
    if bn % 128 and bn != N:
        return False
    ws = 2 * (bm * K + K * bn) * 1 + bm * bn * 4
    return ws <= _vmem_budget()


def _quant_legal(params: Params, config: Config) -> bool:
    return quant_matmul_legal(int(config.get("block_m", 0)),
                              int(config.get("block_n", 0)),
                              params["M"], params["K"], params["N"])


def quant_matmul_candidates(params: Params) -> List[Config]:
    M, K, N = params["M"], params["K"], params["N"]
    # M and N themselves join the grids so shapes below the minimum
    # tile (e.g. a batch-1 bucket) still have the whole-dim candidate
    ms = sorted({b for b in (*QUANT_BLOCK_M, M) if M % b == 0})
    ns = sorted({b for b in (*QUANT_BLOCK_N, N) if N % b == 0})
    return [c for c in ({"block_m": bm, "block_n": bn}
                        for bm in ms for bn in ns)
            if _quant_legal(params, c)]


def quant_matmul_default(params: Params) -> Optional[Config]:
    """The largest legal output tile (fewest grid steps: the int8
    panels are small enough that dispatch overhead, not VMEM, dominates
    at serving shapes). None: no legal tile, the reference contraction
    runs."""
    M, N = params["M"], params["N"]
    for bm in sorted({*QUANT_BLOCK_M, M}, reverse=True):
        for bn in sorted({*QUANT_BLOCK_N, N}, reverse=True):
            if _quant_legal(params, {"block_m": bm, "block_n": bn}):
                return {"block_m": bm, "block_n": bn}
    return None


def _quant_case(params: Params, dtype: str) -> "Case":
    import numpy as np

    import jax

    from ..ops import quant_kernels as qk

    M, K, N = params["M"], params["K"], params["N"]
    rng = np.random.RandomState(0)
    import jax.numpy as jnp

    xq = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (K, N)), jnp.int8)
    args = (xq, wq)

    def make(config: Config) -> Callable[[], Any]:
        from . import overrides

        jf = jax.jit(lambda x, w: qk.quant_matmul(x, w))
        with overrides.forcing("quant_matmul", config):
            jf(*args)
        return lambda: jf(*args)

    def ref():
        return [np.asarray(qk._quant_matmul_ref(xq, wq), np.int64)
                .astype(np.float32)]

    # integer contraction: every candidate must be EXACT, not close
    return Case("quant_matmul", make, ref, tol=0.0)


# --------------------------------------------------------------- registry --
class Case:
    """A runnable tuning case: `make(config)` returns a zero-arg
    compiled thunk (traced while the config override was forced), and
    `reference()` the analytic-lowering outputs for the numeric
    cross-check."""

    def __init__(self, kernel: str, make, reference, tol: float):
        self.kernel = kernel
        self.make = make
        self.reference = reference
        self.tol = tol


class KernelSpace:
    """One family: its shape params, legality of a config at a shape,
    the candidates a sweep walks, the default rule, and `off`: what the
    kernel gets when a forced config is illegal (None where the family
    has an unfused path to fall to)."""

    def __init__(self, name: str, param_names, legal, candidates, default,
                 off=lambda params: None, make_case=None, doc: str = ""):
        self.name = name
        self.param_names = tuple(param_names)
        self.legal = legal
        self.candidates = candidates
        self.default = default
        self.off = off
        self._make_case = make_case
        self.doc = doc

    def shape(self, params: Params, dtype: str) -> Params:
        """The family's params as ints, in canonical order, with dtype."""
        missing = [k for k in self.param_names if k not in params]
        if missing:
            raise ValueError(
                f"{self.name}: missing shape params {missing}; needs "
                f"{list(self.param_names)}")
        norm = {k: int(params[k]) for k in self.param_names}
        norm["dtype"] = dtype
        return norm

    def normalize(self, params: Params, dtype: str) -> Params:
        """shape() for input from outside the program (the CLI): the
        dtype must be one a sweep can build a case for."""
        if dtype not in DTYPES:
            raise ValueError(f"{self.name}: dtype must be one of "
                             f"{DTYPES}, got {dtype!r}")
        return self.shape(params, dtype)

    def make_case(self, params: Params, dtype: str) -> Case:
        if self._make_case is None:
            raise NotImplementedError(
                f"kernel family {self.name!r} has no measurement runner "
                "yet (candidates/--dry-run only)")
        return self._make_case(params, dtype)


FAMILIES: Dict[str, KernelSpace] = {
    "bahdanau_attention": KernelSpace(
        "bahdanau_attention", ("B", "Sp", "A", "C"), _bahdanau_legal,
        bahdanau_candidates, bahdanau_default, make_case=_bahdanau_case,
        doc="batch tile (bblk) of the fused Bahdanau decoder kernels"),
    "flash_attention": KernelSpace(
        "flash_attention", ("Tq", "Tk"), _flash_legal,
        flash_candidates, flash_default,
        off=flash_default,  # the kernels have no unfused path to fall to
        make_case=_flash_case,
        doc="q/k block sizes of the TPU flash-attention kernel"),
    "fused_lstm": KernelSpace(
        "fused_lstm", ("B", "H"), _rnn_legal("lstm"),
        _rnn_candidates("lstm"), _rnn_default("lstm"),
        off=lambda params: {"fused": False},
        doc="fused-vs-scan dispatch of the whole-sequence LSTM kernel"),
    "fused_gru": KernelSpace(
        "fused_gru", ("B", "H"), _rnn_legal("gru"),
        _rnn_candidates("gru"), _rnn_default("gru"),
        off=lambda params: {"fused": False},
        doc="fused-vs-scan dispatch of the whole-sequence GRU kernel"),
    "quant_matmul": KernelSpace(
        "quant_matmul", ("M", "K", "N"), _quant_legal,
        quant_matmul_candidates, quant_matmul_default,
        make_case=_quant_case,
        doc="output tile (block_m, block_n) of the int8×int8→int32 "
            "quantized-matmul kernel"),
}

ALIASES = {"bahdanau": "bahdanau_attention", "attention": "bahdanau_attention",
           "flash": "flash_attention",
           "lstm": "fused_lstm", "gru": "fused_gru",
           "quant": "quant_matmul", "int8": "quant_matmul"}


def get_family(name: str) -> KernelSpace:
    key = ALIASES.get(name, name)
    if key not in FAMILIES:
        raise KeyError(
            f"unknown kernel family {name!r}; known: "
            f"{sorted(FAMILIES)} (aliases {sorted(ALIASES)})")
    return FAMILIES[key]


def pick(family: str, params: Params, dtype: str) -> Optional[Config]:
    """THE choice of a family's config at a shape, called by the kernels
    at trace time: the forced config where one is and it is legal here;
    a warning and the family's `off` where it is forced and illegal
    (someone pinned exactly that tile for a sweep: another in its place
    would falsify the sweep); else the default rule. None: the kernel
    does not run at this shape."""
    fam = FAMILIES[family]
    shape = fam.shape(params, dtype)
    forced = overrides.forced_config(family)
    if forced is None:
        return fam.default(shape)
    if fam.legal(shape, forced.config):
        return forced.config
    off = fam.off(shape)
    warnings.warn(
        f"forced {family} config {forced.config} fails eligibility at "
        f"{shape} (divisibility or VMEM); "
        + ("the fused kernel is DISABLED for this shape" if off is None
           else f"using {off}"), stacklevel=3)
    return off


# ------------------------------------------------- model program sweep --
def cases_from_program(program=None, dp: int = 1) -> List[Dict[str, Any]]:
    """Best-effort scan of a Program for tunable kernel sites with
    concrete shapes: returns [{family, params, dtype, op}] — the CLI's
    `tune --config model.py` sweep source. Sites whose shapes aren't
    fully concrete (e.g. -1 batch) are skipped; the per-kernel
    `--kernel/--shape` path covers those.

    `dp` is the data-parallel degree the model will RUN under: the
    fused kernels dispatch inside shard_map at the PER-SHARD batch
    (ops/mesh_dispatch.local_batch — ADVICE.md's per-shard eligibility
    lesson), so a sweep must time the per-shard shape too, or it times
    a shape that never dispatches. Batch-carrying params divide by dp;
    non-divisible sites are skipped (the runtime falls back to the
    scan/XLA formulation there — nothing to tune)."""
    from ..core.program import default_main_program

    program = program or default_main_program()
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    amp_dt = "bfloat16" if getattr(program, "amp_dtype", None) else "float32"
    out = []

    def var_shape(block, name):
        try:
            return [int(d) for d in block.var(name).shape]
        except (KeyError, TypeError, ValueError):
            return None

    for block in program.blocks:
        for op in block.ops:
            if op.type == "flash_attention":
                # only the sequence lengths key the flash space — a -1
                # batch dim (the usual data() declaration) is fine
                s = var_shape(block, op.inputs["Q"][0])
                k = var_shape(block, op.inputs["K"][0])
                if not s or not k or len(s) < 3 or s[1] <= 0 or k[1] <= 0:
                    continue
                out.append({"family": "flash_attention",
                            "params": {"Tq": s[1], "Tk": k[1]},
                            "dtype": amp_dt, "op": op.type})
            elif op.type == "attention_gru_decoder":
                enc = var_shape(block, op.inputs["EncState"][0])
                wa = var_shape(block, op.inputs["WaEnc"][0])
                h0 = var_shape(block, op.inputs["H0"][0])
                if not enc or not wa or not h0 or h0[0] <= 0:
                    continue
                if h0[0] % dp:
                    continue  # ragged shard: runtime scans, nothing to tune
                src = int(op.attrs.get("src_max_len") or 0)
                if src <= 0:
                    continue
                out.append({"family": "bahdanau_attention",
                            "params": {"B": h0[0] // dp, "Sp": pad_s(src),
                                       "A": wa[1], "C": enc[-1]},
                            "dtype": amp_dt, "op": op.type})
            elif op.type in ("quantized_mul", "quantized_matmul"):
                # int8 sites (quant/convert.py rewrite): the weight
                # panel [K, N] is static; the row count comes from X
                # when concrete
                x = var_shape(block, op.inputs["X"][0])
                w = var_shape(block, op.inputs["Y"][0])
                if not x or not w or len(w) != 2 or min(w) <= 0:
                    continue
                xd = int(op.attrs.get("x_num_col_dims", 1))
                lead = x[:xd]
                if any(d <= 0 for d in lead):
                    continue
                m = 1
                for d in lead:
                    m *= d
                if m % dp:
                    continue
                out.append({"family": "quant_matmul",
                            "params": {"M": m // dp, "K": w[0],
                                       "N": w[1]},
                            "dtype": "int8", "op": op.type})
            # dynamic_lstm/dynamic_gru sites are LoD-batched: their
            # runtime batch is not static in the program, so the model
            # sweep skips them — tune those via --kernel lstm/gru with
            # an explicit --shape B=...,H=...
    return out
