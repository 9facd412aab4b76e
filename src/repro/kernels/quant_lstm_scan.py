"""Persistent Pallas sequence kernel: the integer recurrent stage, any cell.

One ``pallas_call`` runs the ENTIRE sequence: the grid is ``(T,)`` (TPU grid
iteration is sequential), every recurrent-stage array (packed weights,
peephole / LN / projection parameters -- whatever the cell's quantizer
emitted) is mapped to a constant-index block so it stays resident in VMEM
across steps, and the cell's flat state tuple (``core/cell.py``:
``state_leaves``) lives in VMEM scratch for the whole sweep -- one scratch
buffer per leaf, seeded at ``t == 0``.  Each grid step fuses

    recurrent matmul (int8 MXU)  ->  per-gate fixed-point rescales
    [-> integer LayerNorm / peephole]  ->  cell update
    [-> projection matmul]  ->  write ys[t], update the carry

which eliminates the per-timestep dispatch overhead and the per-step state
HBM round-trips the scan-of-steps executor pays: between consecutive
timesteps nothing leaves VMEM.  The input-dependent work arrives
precomputed -- the kernel consumes per-step ``(B, G*H)`` int32 blocks of
the hoisted time-batched input GEMM (``ops.quant_recurrent_input_proj``),
laid out time-major so each block spans the array's last two dims (the
TPU lowering tiles those by (8, 128) unless they are full extents), so
the only matmul on the critical scan path is the genuinely sequential
``h_{t-1} @ R_cat`` product.

The step math is ``ref.recurrent_step_jnp`` -- the same cell dispatch the
``xla`` scan executor runs -- traced inside the kernel body, so the two
lowerings are bit-identical by construction (integer ops only; validated
against the goldens and the per-gate reference for all 16 LSTM variants and
both GRU variants).  The cell's arrays dict is flattened with
``jax.tree_util`` (deterministic key order) into one ref per leaf and
rebuilt inside the kernel, so a new cell needs NO kernel changes: whatever
``quantize_<cell>_layer`` packs simply rides along into VMEM.

The masked variant takes a per-row ``valid_len`` and freezes every state
leaf for rows past their valid prefix -- the chunked-prefill contract of
``ops.quant_recurrent_seq_masked``.

Sizing note: blocks span the full ``(B, ...)`` extents (integer LayerNorm
reduces over the whole hidden axis, and the carry must stay resident), so
``B * (G*H)`` int32 plus the packed weights must fit in VMEM: at H = 2048
(``lstm-rnnt``, ``gru-rnnt``) and B <= 8 they fit the v5e's 16 MB scoped
limit (``tests/test_tpu_compile.py``).  Time is the grid, so T is unbounded.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import cell as C

from . import ref

# Consumed by the hoisted input GEMM, never by the recurrent stage.
_INPUT_GEMM_KEYS = ("W_cat", "fold_x_cat")

# Rows of a 32-bit TPU vector register: the kernel's batch is padded to a
# multiple of this (see ``quant_recurrent_seq_scan_pallas``), and the
# integer LM's head runs its rows in blocks of this size
# (``lstm_lm._quant_head``).
SUBLANES = 8


def _recurrent_vals(arrays: Dict[str, Any]):
    """Deterministic flat view of the recurrent-stage arrays.

    ``jax.tree_util`` flattens dicts in sorted-key order, so the leaf list
    and its treedef are a stable function of the arrays' key structure --
    the kernel rebuilds the dict from one ref per leaf.
    """
    rec = {k: v for k, v in arrays.items() if k not in _INPUT_GEMM_KEYS}
    return jax.tree_util.tree_flatten(rec)


def _scan_kernel(*refs, spec, treedef, n_vals: int, n_state: int,
                 masked: bool):
    it = iter(refs)
    acc_ref = next(it)  # (B, G*H) int32: step slice of the hoisted GEMM
    val_refs = [next(it) for _ in range(n_vals)]  # VMEM-resident all sweep
    s0_refs = [next(it) for _ in range(n_state)]  # t=0 carry seeds
    vl_ref = next(it) if masked else None
    ys_ref = next(it)
    out_refs = [next(it) for _ in range(n_state)]  # final carry outputs
    scrs = [next(it) for _ in range(n_state)]  # VMEM carry, one per leaf

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _seed_carry():
        for scr, s0 in zip(scrs, s0_refs):
            scr[...] = s0[...]

    state = tuple(scr[...] for scr in scrs)
    vals = jax.tree_util.tree_unflatten(treedef, [r[...] for r in val_refs])
    new_state = ref.recurrent_step_jnp(
        vals, spec, acc_ref[...], state)
    if masked:
        live = vl_ref[...] > t  # (B, 1): broadcasts over every leaf's width
        new_state = tuple(
            jnp.where(live, new, old)
            for new, old in zip(new_state, state))
    ys_ref[...] = new_state[0]  # leaf 0 is the emitted output
    for scr, new in zip(scrs, new_state):
        scr[...] = new

    @pl.when(t == pl.num_programs(0) - 1)
    def _emit_final_state():
        for out, new in zip(out_refs, new_state):
            out[...] = new


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def quant_recurrent_seq_scan_pallas(
    arrays: Dict[str, Any],
    spec,  # core.recipe.Q*Spec (static, names the cell)
    acc_x_all: jax.Array,  # int32 (B, T, G*H): hoisted input accumulator
    state0: Tuple[jax.Array, ...],  # per cell.state_leaves(spec)
    valid_len: Optional[jax.Array] = None,  # int32 (B,): masked variant
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Run the recurrent stage for a whole sequence in ONE kernel launch.

    Returns ``(ys int8 (B, T, d_out), state_final)`` -- bit-identical to
    scanning ``ops.quant_recurrent_step`` over the same slices.

    The batch runs padded to whole sublane tiles (B=1, the single-stream
    decode shape, runs as 8 rows).  Below 8 rows the TPU compiler lays the
    ``(B, G*H)`` intermediates out with partial-tile layouts, and at
    ``lstm-rnnt`` width a B=1 kernel then asks for 20.5 MB of scoped VMEM
    (limit 16 MB) after minutes of compiling; padded, it compiles like B=8.
    Rows never interact (per-row matmuls, LayerNorm over the hidden axis
    only), so the padding rows are dropped and the real rows are unchanged.
    """
    B = acc_x_all.shape[0]
    B_pad = -(-B // SUBLANES) * SUBLANES
    if B_pad != B:
        def pad(x):
            return jnp.pad(x, [(0, B_pad - B)] + [(0, 0)] * (x.ndim - 1))

        ys, state = quant_recurrent_seq_scan_pallas(
            arrays, spec, pad(acc_x_all), tuple(pad(s) for s in state0),
            None if valid_len is None else pad(valid_len),
            interpret=interpret)
        return ys[:B], tuple(s[:B] for s in state)
    B, T, GH = acc_x_all.shape
    cell = C.get_cell(spec)
    leaves = cell.state_leaves(spec)
    d_out = cell.d_out(spec)
    masked = valid_len is not None
    state0 = tuple(state0)
    vals_flat, treedef = _recurrent_vals(arrays)

    def const(shape):
        """Whole-array block revisited every grid step (stays in VMEM)."""
        return pl.BlockSpec(shape, lambda t, _n=len(shape): (0,) * _n)

    def step_block(width):
        """Time-major ``(T, B, width)`` array, one ``(B, width)`` step per
        grid index: its last two block dims are the array's full extents,
        which Mosaic accepts at any B and width."""
        return pl.BlockSpec((pl.squeezed, B, width), lambda t: (t, 0, 0))

    inputs = [jnp.swapaxes(acc_x_all, 0, 1), *vals_flat, *state0]
    in_specs = [step_block(GH)]
    in_specs += [const(v.shape) for v in vals_flat]
    in_specs += [const((B, leaf.width)) for leaf in leaves]
    if masked:
        # (B, 1), not (B,): a 1-D mask would need a vector reshape to
        # broadcast over the leaves, which Mosaic cannot lower
        inputs.append(valid_len.reshape(B, 1))
        in_specs.append(const((B, 1)))

    outs = pl.pallas_call(
        functools.partial(
            _scan_kernel, spec=spec, treedef=treedef,
            n_vals=len(vals_flat), n_state=len(leaves), masked=masked),
        grid=(T,),
        in_specs=in_specs,
        out_specs=(
            [step_block(d_out)]
            + [const((B, leaf.width)) for leaf in leaves]
        ),
        out_shape=(
            [jax.ShapeDtypeStruct((T, B, d_out), jnp.int8)]
            + [jax.ShapeDtypeStruct((B, leaf.width), leaf.dtype)
               for leaf in leaves]
        ),
        scratch_shapes=[
            pltpu.VMEM((B, leaf.width), leaf.dtype) for leaf in leaves
        ],
        interpret=interpret,
    )(*inputs)
    return jnp.swapaxes(outs[0], 0, 1), tuple(outs[1:])


def quant_lstm_seq_scan_pallas(
    arrays: Dict[str, Any],
    spec,  # core.recipe.QLSTMSpec (static)
    acc_x_all: jax.Array,
    h0_q: jax.Array,
    c0_q: jax.Array,
    valid_len: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """LSTM-shaped wrapper kept for callers that thread ``(h0, c0)``."""
    ys, state = quant_recurrent_seq_scan_pallas(
        arrays, spec, acc_x_all, (h0_q, c0_q), valid_len,
        interpret=interpret)
    return ys, state
