"""Sparse batch inputs and sparse (row-wise) gradients.

Reference coverage:
- Sparse input slots: paddle/py_paddle/dataprovider_converter.py:154,184
  (SparseBinaryScanner / SparseFloatScanner building CSR Arguments) backed
  by paddle/math/CpuSparseMatrix.h — wide CTR-style features fed as
  index[/value] lists per sample.
- Sparse gradients: paddle/framework/selected_rows.h (SelectedRows = rows +
  value tensor, the Fluid sparse-grad type emitted by
  lookup_table_op.cc when is_sparse) and Gen-1's
  paddle/math/SparseRowMatrix.h (sparse-row update storage).

TPU-native design: XLA wants static shapes, so a sparse batch is stored in
*padded-COO* form with a bucketed nonzero capacity (the same trick
core/lod.py uses for ragged sequences):

  indices : [cap] int32   column index of each nonzero (padding slots 0)
  values  : [cap] f32     value of each nonzero (1.0 for binary; padding 0)
  rowids  : [cap] int32   batch row of each nonzero; padding slots = batch
                          (out of range, dropped by segment_sum)
  batch   : static int    number of rows (pytree aux — shapes depend on it)
  dim     : static int    feature dimension

A sparse × dense matmul is then gather-rows + weighted segment-sum — a
bandwidth-bound gather feeding the MXU-friendly dense tail, with no [N, dim]
densification. SelectedRows carries row-wise gradients (rows, values) so a
huge embedding/FC table never materializes a dense gradient; optimizer ops
apply row-wise (lazy) updates via scatter — see ops/optimizer_ops.py.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


@jax.tree_util.register_pytree_node_class
class SparseArray:
    """A batch of sparse feature vectors in padded-COO form (module doc)."""

    def __init__(self, indices, values, rowids, batch: int, dim: int):
        self.indices = indices
        self.values = values
        self.rowids = rowids
        self.batch = int(batch)
        self.dim = int(dim)

    # -- pytree protocol: batch/dim are static (they set output shapes) ----
    def tree_flatten(self):
        return (self.indices, self.values, self.rowids), (self.batch, self.dim)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, batch=aux[0], dim=aux[1])

    @property
    def capacity(self) -> int:
        return int(self.indices.shape[0])

    @property
    def dtype(self):
        return self.values.dtype

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_batch(
        samples: Sequence,
        dim: int,
        format: str = "binary",
        capacity: Optional[int] = None,
        bucket: int = 128,
        dtype=np.float32,
    ) -> "SparseArray":
        """Build from per-sample nonzero lists (host side).

        format="binary": each sample is a list of active column indices
        (SparseBinaryScanner parity). format="float": each sample is a list
        of (index, value) pairs (SparseFloatScanner parity).
        """
        n = len(samples)
        flat_idx, flat_val, flat_row = [], [], []
        for r, s in enumerate(samples):
            if format == "binary":
                for i in s:
                    flat_idx.append(int(i))
                    flat_val.append(1.0)
                    flat_row.append(r)
            elif format == "float":
                for i, v in s:
                    flat_idx.append(int(i))
                    flat_val.append(float(v))
                    flat_row.append(r)
            else:
                raise ValueError(f"unknown sparse format {format!r}")
        nnz = len(flat_idx)
        cap = capacity or max(_round_up(max(nnz, 1), bucket), bucket)
        if nnz > cap:
            raise ValueError(f"batch nonzeros {nnz} exceed capacity {cap}")
        idx = np.zeros((cap,), np.int32)
        val = np.zeros((cap,), dtype)
        row = np.full((cap,), n, np.int32)  # padding rows out of range
        idx[:nnz] = flat_idx
        val[:nnz] = flat_val
        row[:nnz] = flat_row
        bad = [i for i in flat_idx if i < 0 or i >= dim]
        if bad:
            raise ValueError(f"sparse index {bad[0]} out of range [0, {dim})")
        return SparseArray(
            jnp.asarray(idx), jnp.asarray(val), jnp.asarray(row),
            batch=n, dim=dim,
        )

    # -- ops ---------------------------------------------------------------
    def matmul(self, w) -> jnp.ndarray:
        """self @ w for dense w [dim, out]: gather + weighted segment-sum."""
        rows = jnp.take(w, self.indices, axis=0)  # [cap, out]
        contrib = rows * self.values[:, None].astype(rows.dtype)
        return jax.ops.segment_sum(
            contrib, self.rowids, num_segments=self.batch
        )

    def to_dense(self) -> jnp.ndarray:
        """[batch, dim] densification (tests / small dims only)."""
        out = jnp.zeros((self.batch, self.dim), self.values.dtype)
        # padding slots have rowids == batch → dropped by scatter's default
        # out-of-bounds-drop semantics under jit
        return out.at[self.rowids, self.indices].add(self.values, mode="drop")


@jax.tree_util.register_pytree_node_class
class SelectedRows:
    """Row-wise sparse gradient: (rows, values) of a [num_rows, D] tensor.

    Reference: paddle/framework/selected_rows.h. rows may repeat (one entry
    per lookup occurrence); the semantic dense value is
    zeros.at[rows].add(values). Rows == num_rows are padding (dropped).
    """

    def __init__(self, rows, values, num_rows: int):
        self.rows = rows          # [k] int32
        self.values = values      # [k, D]
        self.num_rows = int(num_rows)

    def tree_flatten(self):
        return (self.rows, self.values), (self.num_rows,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, num_rows=aux[0])

    @property
    def shape(self):
        return (self.num_rows,) + tuple(self.values.shape[1:])

    @property
    def dtype(self):
        return self.values.dtype

    def to_dense(self) -> jnp.ndarray:
        out = jnp.zeros(self.shape, self.values.dtype)
        return out.at[self.rows].add(self.values, mode="drop")

    def dedup(self):
        """(unique_rows, summed_values) with static shapes.

        Duplicate occurrences of a row are summed (the dense-equivalent
        gradient); fill slots get row == num_rows (dropped on scatter).
        Needed by moment-based optimizers where the update is nonlinear in
        the gradient (adam/adagrad: two half-gradients != one gradient).
        """
        k = self.rows.shape[0]
        uniq, inv = jnp.unique(
            self.rows, size=k, fill_value=self.num_rows, return_inverse=True
        )
        summed = jnp.zeros_like(self.values).at[inv.reshape(self.rows.shape)].add(self.values)
        return uniq, summed

    def __mul__(self, scalar):
        return SelectedRows(self.rows, self.values * scalar, self.num_rows)

    __rmul__ = __mul__


class SparseGradTape:
    """Trace-time bridge between the autodiff lowering and lookup sites.

    For a parameter marked sparse_update, a dense [vocab, dim] gradient must
    never exist. Trick: every gather site computes
        out = stop_gradient(W)[ids] + slot
    where `slot` is a zeros array that IS a differentiated input of the loss
    closure. d(loss)/d(slot) is exactly the cotangent of the gathered rows,
    so jax.grad over the slots yields the SelectedRows values and the
    recorded `ids` give the rows — without W ever appearing in the
    differentiated inputs. Static shapes hold because feeds are
    shape-bucketed (core/lod.py / SparseArray).

    Two passes of ONE closure share the tape protocol (core/executor.py
    _run_autodiff; there is no third, plain trace of the forward ops):
    - discovery (slots=None, under jax.eval_shape: abstract, nothing runs
      on the device): records each site's (param, shape, dtype); next_slot
      returns zeros.
    - apply (slots=list of tracers, under jax.value_and_grad: the step's
      only forward pass): next_slot hands out the tracers in the same
      deterministic trace order; record_site collects the traced row ids
      per site, returned in the closure's aux output beside the forward's
      environment.
    """

    def __init__(self, sparse_params, slots=None):
        self.sparse_params = set(sparse_params)
        self.slots = slots
        self.sites = []    # [(param_name, shape, dtype)] (discovery order)
        self.ids_out = []  # apply mode: traced rows per site
        self._i = 0

    def wants(self, param_name: str) -> bool:
        return param_name in self.sparse_params

    def next_slot(self, gathered):
        if self.slots is None:
            self.sites.append((None, gathered.shape, gathered.dtype))
            return jnp.zeros(gathered.shape, gathered.dtype)
        slot = self.slots[self._i]
        self._i += 1
        return slot

    def record_site(self, param_name: str, rows) -> None:
        if self.slots is None:
            self.sites[-1] = (param_name, *self.sites[-1][1:])
        self.ids_out.append((param_name, rows))
