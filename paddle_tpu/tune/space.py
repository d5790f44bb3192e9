"""Per-kernel candidate spaces: legality predicates + generators.

THE design rule of this module: the legality model is defined ONCE and
shared by the tuner and the runtime. `ops/bahdanau_kernels._bblk`
imports `bahdanau_blk_legal` from here; `ops/flash_ops` imports
`flash_block_legal`.
So a candidate this module emits is exactly a config the runtime will
accept, and a config the runtime accepts is exactly one this module can
enumerate — the tuner can never measure a config that later fails to
lower, and the property test (tests/test_tune.py) pins the equivalence.

Legality has two ingredients per family:
- Mosaic tile rules: the last-two-dims (8k, 128k)-or-full block-shape
  rule (see `bahdanau_blk_legal`), lane
  alignment, and divide-the-array constraints;
- the VMEM-budget working-set models lifted from the kernels (sized
  against the 15 MiB scoped budget in ops/pallas_kernels._VMEM_BUDGET,
  which reproduces every measured compile overflow — see its comment).

Anything in `ops/` is imported lazily: this module loads during
`paddle_tpu.core` import (via tune.overrides via the Executor), before
the ops package exists.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

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


# io dtypes the spaces can key on: int8 joined with the quantized-matmul
# family (the serving fast path) — tuned int8 is just another column of
# the same per-device table.
DTYPES = ("bfloat16", "float32", "int8")


def _itemsize(dtype_name: str) -> int:
    return {"bfloat16": 2, "float32": 4, "int8": 1}[dtype_name]


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


def bahdanau_candidates(params: Params) -> List[Config]:
    B, Sp, A, C = params["B"], params["Sp"], params["A"], params["C"]
    item = _itemsize(params["dtype"])
    out = []
    for b in range(1, B + 1):
        if B % b == 0 and bahdanau_blk_legal(b, B, Sp, A, C, item):
            out.append({"bblk": b})
    return out


def bahdanau_default(params: Params) -> Optional[Config]:
    """The runtime's analytic choice (bahdanau_kernels._bblk fallback
    order): 8 measured best on v5e at the NMT shapes; 4 and 2 for small
    batches only."""
    B, Sp, A, C = params["B"], params["Sp"], params["A"], params["C"]
    item = _itemsize(params["dtype"])
    for b in (8, 4, 2):
        if bahdanau_blk_legal(b, B, Sp, A, C, item):
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
    """The TPU flash kernel requires blocks to DIVIDE the sequence and
    be lane-aligned (128) — ops/flash_ops._v5e_block_sizes rounds its
    target down through exactly this predicate."""
    return (bq > 0 and bk > 0 and bq % 128 == 0 and bk % 128 == 0
            and Tq % bq == 0 and Tk % bk == 0)


def flash_candidates(params: Params) -> List[Config]:
    Tq, Tk = params["Tq"], params["Tk"]
    qs = [b for b in FLASH_BLOCK_GRID if flash_block_legal(b, 128, Tq, 128)]
    ks = [b for b in FLASH_BLOCK_GRID if flash_block_legal(128, b, 128, Tk)]
    return [{"block_q": q, "block_k": k} for q in qs for k in ks]


def flash_default(params: Params) -> Optional[Config]:
    """The v5e-tuned heuristic (flash_ops._v5e_block_sizes): 512-wide
    blocks up to T=4096, 1024 from 8192, rounded down to a divisor."""
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
    backward-kernel VMEM model from ops/pallas_kernels — everything in
    lstm_supported/gru_supported EXCEPT the measured H-window, which is
    exactly the judgment the tuner replaces."""
    from ..ops import pallas_kernels as pk

    if not (B >= 8 and B % 8 == 0 and H % 128 == 0):
        return False
    g = 4 if kind == "lstm" else 3
    dw_max = (pk._LSTM_FUSED_DW_MAX_H if kind == "lstm"
              else pk._GRU_FUSED_DW_MAX_H)
    return pk._bwd_vmem_bytes(B, H, g, itemsize, dw_max) <= pk._VMEM_BUDGET


def _rnn_candidates(kind: str):
    def gen(params: Params) -> List[Config]:
        out = [{"fused": False}]
        if _rnn_hard_ok(kind, params["B"], params["H"],
                        _itemsize(params["dtype"])):
            out.insert(0, {"fused": True})
        return out

    return gen


def _rnn_default(kind: str):
    def default(params: Params) -> Config:
        B, H = params["B"], params["H"]
        if not _rnn_hard_ok(kind, B, H, _itemsize(params["dtype"])):
            return {"fused": False}
        # the measured windows (rnn_kernel_microbench: old link, rounds
        # <= 5, not re-measured on this chip; record in git history)
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


def quant_matmul_candidates(params: Params) -> List[Config]:
    M, K, N = params["M"], params["K"], params["N"]
    # M and N themselves join the grids so shapes below the minimum
    # tile (e.g. a batch-1 bucket) still have the whole-dim candidate
    ms = sorted({b for b in (*QUANT_BLOCK_M, M) if M % b == 0})
    ns = sorted({b for b in (*QUANT_BLOCK_N, N) if N % b == 0})
    return [{"block_m": bm, "block_n": bn}
            for bm in ms for bn in ns
            if quant_matmul_legal(bm, bn, M, K, N)]


def quant_matmul_default(params: Params) -> Optional[Config]:
    """Analytic choice of the runtime fallback: the largest legal
    output tile (fewest grid steps — the int8 panels are small enough
    that dispatch overhead, not VMEM, dominates at serving shapes)."""
    M, K, N = params["M"], params["K"], params["N"]
    best = None
    for bm in sorted({*QUANT_BLOCK_M, M}, reverse=True):
        if M % bm:
            continue
        for bn in sorted({*QUANT_BLOCK_N, N}, reverse=True):
            if N % bn:
                continue
            if quant_matmul_legal(bm, bn, M, K, N):
                return {"block_m": bm, "block_n": bn}
    return best


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
    def __init__(self, name: str, param_names, candidates, default,
                 make_case=None, doc: str = ""):
        self.name = name
        self.param_names = tuple(param_names)
        self._candidates = candidates
        self._default = default
        self._make_case = make_case
        self.doc = doc

    def normalize(self, params: Params, dtype: str) -> Params:
        """Validated, canonically-ordered params incl. dtype — the shape
        signature the cache keys on."""
        if dtype not in DTYPES:
            raise ValueError(f"{self.name}: dtype must be one of "
                             f"{DTYPES}, got {dtype!r}")
        missing = [k for k in self.param_names if k not in params]
        if missing:
            raise ValueError(
                f"{self.name}: missing shape params {missing}; needs "
                f"{list(self.param_names)}")
        norm = {k: int(params[k]) for k in self.param_names}
        norm["dtype"] = dtype
        return norm

    def candidates(self, params: Params) -> List[Config]:
        return self._candidates(params)

    def default(self, params: Params) -> Optional[Config]:
        return self._default(params)

    def make_case(self, params: Params, dtype: str) -> Case:
        if self._make_case is None:
            raise NotImplementedError(
                f"kernel family {self.name!r} has no measurement runner "
                "yet (candidates/--dry-run only)")
        return self._make_case(params, dtype)


FAMILIES: Dict[str, KernelSpace] = {
    "bahdanau_attention": KernelSpace(
        "bahdanau_attention", ("B", "Sp", "A", "C"),
        bahdanau_candidates, bahdanau_default, _bahdanau_case,
        doc="batch tile (bblk) of the fused Bahdanau decoder kernels"),
    "flash_attention": KernelSpace(
        "flash_attention", ("Tq", "Tk"),
        flash_candidates, flash_default, _flash_case,
        doc="q/k block sizes of the TPU flash-attention kernel"),
    "fused_lstm": KernelSpace(
        "fused_lstm", ("B", "H"),
        _rnn_candidates("lstm"), _rnn_default("lstm"),
        doc="fused-vs-scan dispatch of the whole-sequence LSTM kernel"),
    "fused_gru": KernelSpace(
        "fused_gru", ("B", "H"),
        _rnn_candidates("gru"), _rnn_default("gru"),
        doc="fused-vs-scan dispatch of the whole-sequence GRU kernel"),
    "quant_matmul": KernelSpace(
        "quant_matmul", ("M", "K", "N"),
        quant_matmul_candidates, quant_matmul_default, _quant_case,
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


def config_legal(family: str, params: Params, dtype: str,
                 config: Config) -> bool:
    """Is `config` a legal candidate for `params` — i.e. would the
    candidate generator itself have emitted it? THE re-validation gate
    for shape-interpolated lookups (tune/overrides.py): a config tuned
    at a NEIGHBORING shape is only usable at the target shape if it is
    inside the target's own candidate set, so an interpolated consult
    can never hand the runtime a tile its legality model rejects.
    Membership (not just predicate re-evaluation) is deliberate: the
    generators encode extra structure — divisor grids, the fixed block
    lists — that a bare predicate check would miss. Malformed
    params/config degrade to False, never raise (interpolation feeds
    arbitrary table contents through here)."""
    try:
        fam = get_family(family)
        norm = fam.normalize(params, dtype)
        return dict(config) in fam.candidates(norm)
    except (KeyError, ValueError, TypeError):
        return False


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
    lesson), so tuning must key on the per-shard shape too, or every
    mesh run misses the table and a global-batch entry tunes a shape
    that never dispatches. Batch-carrying params divide by dp;
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
                # when concrete (serving buckets expand the -1 case via
                # engine.decode_tune_cases)
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
