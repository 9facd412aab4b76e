"""Stacked recurrent language model: the paper's architecture as a config.

10 layers x 2048 hidden (the RNN-T encoder stack of [Sak et al.] / the
paper's Table 1 models), embedding + softmax head.  Supports float
training/serving and -- via the repro.core recipe -- fully integer-only
serving (see examples/serve_quantized.py).

Cell-agnostic since PR 8: ``cfg.rnn_cell`` selects the recurrent cell
(``"lstm"`` -- the paper's LN+projection topology with a 640-wide
projection; or ``"gru"`` -- the LN reset-after GRU, no projection stage).
The stacked decode state is ``{<cell state keys...>: [per-layer arrays],
"len": counter}`` (LSTM ``{"h", "c", "len"}``, GRU ``{"h", "len"}``); every
state helper below (init/reset/slice/stack/write) iterates the cell's
declared leaves, so the serving engine, state pool, and speculation paths
never name a leaf.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.layers import embedding as emb
from repro.models import gru as G
from repro.models import lstm as L

def rnn_cell(cfg: ArchConfig) -> str:
    """The stack's recurrent cell name (pre-PR-8 configs mean LSTM)."""
    return getattr(cfg, "rnn_cell", "lstm")


def state_keys(cfg: ArchConfig) -> Tuple[str, ...]:
    """Ordered state pytree keys of the stack's cell (leaf 0 = output)."""
    from repro.core import cell as rc

    return rc.CELLS[rnn_cell(cfg)].state_key_names


def d_proj(cfg):
    """Projection width: 2048 -> 640 (Sak et al. ratio 5/16)."""
    return max(cfg.d_rnn * 5 // 16, 8)


def stack_d_out(cfg: ArchConfig) -> int:
    """Per-layer output width (what the LM head consumes)."""
    return d_proj(cfg) if rnn_cell(cfg) == "lstm" else cfg.d_rnn


def layer_cfgs(cfg: ArchConfig):
    out = []
    for i in range(cfg.n_layers):
        d_in = cfg.d_model if i == 0 else stack_d_out(cfg)
        if rnn_cell(cfg) == "gru":
            out.append(G.GRUConfig(
                d_in, cfg.d_rnn, G.GRUVariant(use_layernorm=True)))
        else:
            variant = L.LSTMVariant(use_layernorm=True, use_projection=True)
            out.append(L.LSTMConfig(d_in, cfg.d_rnn, d_proj(cfg), variant))
    return out


def init_params(key, cfg: ArchConfig) -> Tuple[Dict, Dict]:
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    ks = jax.random.split(key, cfg.n_layers + 2)
    emb.embed_init(ks[0], cfg.vocab_size, cfg.d_model, params, specs, tie=True)
    # head consumes the stack's output width, not d_model
    head = (jax.random.normal(ks[-1], (stack_d_out(cfg), cfg.vocab_size),
                              jnp.float32) * 0.02).astype(jnp.bfloat16)
    params["lm_head"], specs["lm_head"] = head, ("embed", "vocab")
    init_layer = (G.init_gru_params if rnn_cell(cfg) == "gru"
                  else L.init_lstm_params)
    # params key stays "lstm" for every cell: it names the recurrent stack
    # slot checkpoints/shardings were built around, not the cell inside it
    params["lstm"] = [
        jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32),
            init_layer(ks[i + 1], lc))
        for i, lc in enumerate(layer_cfgs(cfg))
    ]
    # matrices shard ("embed", "mlp"); vectors shard ("mlp",)
    specs["lstm"] = [
        jax.tree_util.tree_map(
            lambda x: ("embed", "mlp") if x.ndim == 2 else ("mlp",), p)
        for p in params["lstm"]
    ]
    return params, specs


def _float_layer(p, lc, x, layer_states, collector, qat):
    """One float layer step -> (ys, per-layer state tuple, leaf 0 = output).

    ``qat`` reaches only the LSTM (the QAT experiments target the paper's
    own topology); the GRU float graph is baseline + calibration only.
    """
    if isinstance(lc, G.GRUConfig):
        h0 = None if layer_states is None else layer_states[0]
        ys, h = G.gru_layer(p, lc, x, h0, collector=collector)
        return ys, (h,)
    h0, c0 = (None, None) if layer_states is None else layer_states
    ys, (h, c) = L.lstm_layer(p, lc, x, h0, c0, collector=collector, qat=qat)
    return ys, (h, c)


def forward(params, cfg: ArchConfig, tokens, constrain, mesh=None,
            train: bool = False, states=None, collector=None, qat=False):
    keys = state_keys(cfg)
    x = emb.embed_tokens(params, tokens).astype(jnp.float32)
    x = constrain(x, ("batch", "seq", "embed"))
    new_states = []
    for i, (p, lc) in enumerate(zip(params["lstm"], layer_cfgs(cfg))):
        col = _prefixed(collector, f"l{i}/") if collector is not None else None
        layer_states = (None if states is None else
                        tuple(states[k][i] for k in keys))
        x, st = _float_layer(p, lc, x, layer_states, col, qat)
        new_states.append(st)
    logits = emb.logits_head(params, x.astype(jnp.bfloat16))
    logits = constrain(logits, ("batch", "seq", "vocab"))
    if states is None:
        return logits, None
    out = {k: [s[j] for s in new_states] for j, k in enumerate(keys)}
    out["len"] = states["len"] + tokens.shape[1]
    return logits, out


class _prefixed:
    def __init__(self, collector, prefix):
        self.collector = collector
        self.prefix = prefix

    def tap(self, name, x):
        return self.collector.tap(self.prefix + name, x)


def loss_fn(params, cfg: ArchConfig, batch, constrain, mesh=None, qat=False):
    logits, _ = forward(params, cfg, batch["tokens"], constrain, mesh,
                        train=True, qat=qat)
    return emb.cross_entropy(logits, batch["labels"])


def init_decode_state(cfg: ArchConfig, batch: int):
    widths = {"h": stack_d_out(cfg), "c": cfg.d_rnn}
    out = {
        k: [jnp.zeros((batch, widths[k]), jnp.float32)
            for _ in range(cfg.n_layers)]
        for k in state_keys(cfg)
    }
    out["len"] = jnp.zeros((), jnp.int32)
    return out


def prefill(params, cfg, tokens, constrain, mesh=None):
    logits, _ = forward(params, cfg, tokens, constrain, mesh)
    return logits[:, -1]


def decode_step(params, cfg, token, states, constrain, mesh=None):
    logits, new_states = forward(params, cfg, token, constrain, mesh,
                                 states=states)
    return logits[:, -1], new_states


# ---------------------------------------------------------------------------
# Integer-only serving (paper Table 1 "integer" rows): the recurrent stack
# runs through core.recipe + the fused executor; embedding and LM head stay
# float at the quantize/dequantize boundary.
# ---------------------------------------------------------------------------


def quantize_stack(params, cfg: ArchConfig, calib_tokens):
    """Calibrate on ``calib_tokens`` and apply the Table-2 recipe per layer.

    Returns a list of ``(arrays, spec)`` pairs (one per recurrent layer) for
    ``quant_forward``; the cell-specific quantizer is picked by the config.
    """
    from repro.core import recipe as R
    from repro.core.calibrate import Stats, TapCollector

    col = TapCollector()
    forward(params, cfg, calib_tokens, lambda x, logical=None: x,
            collector=col)
    stats = Stats()
    stats.merge(jax.device_get(col.snapshot()))
    quantize_layer = (R.quantize_gru_layer if rnn_cell(cfg) == "gru"
                      else R.quantize_lstm_layer)
    return [
        quantize_layer(p, lc, stats, prefix=f"l{i}/")
        for i, (p, lc) in enumerate(zip(params["lstm"], layer_cfgs(cfg)))
    ]


def _quant_state_keys(states) -> Tuple[str, ...]:
    """Cell state keys of a stacked quantized decode state (all but len).

    Order comes from the dict, so use this ONLY where per-key handling is
    order-independent -- under ``jax.jit`` dict pytrees iterate in SORTED
    key order, not the cell's declared leaf order.
    """
    return tuple(k for k in states if k != "len")


def _cell_state_keys(qlayers) -> Tuple[str, ...]:
    """The cell's DECLARED state-leaf order (leaf 0 = output) -- what must
    be used wherever the state dict is zipped with an ordered leaf tuple."""
    from repro.core import cell as rc

    spec = qlayers[0][1]
    return rc.get_cell(spec).state_keys(spec)


def init_quant_decode_state(qlayers, batch: int, per_slot_len: bool = False):
    """Integer decode state: every cell leaf at its declared reset value
    (e.g. int8 hidden at its zero point, int16 cell at zero).

    ``per_slot_len=True`` tracks a per-row ``(batch,)`` token counter instead
    of one scalar -- what the continuous-batching engine needs, since every
    slot is at a different position in its stream.
    """
    from repro.core import cell as rc
    from repro.models.quant_lstm import initial_recurrent_state

    keys = rc.get_cell(qlayers[0][1]).state_keys(qlayers[0][1])
    cols: Dict[str, list] = {k: [] for k in keys}
    for _, spec in qlayers:
        for k, leaf in zip(keys, initial_recurrent_state(spec, batch)):
            cols[k].append(leaf)
    out: Dict[str, Any] = dict(cols)
    out["len"] = jnp.zeros((batch,) if per_slot_len else (), jnp.int32)
    return out


def reset_quant_slot(qlayers, states, slot):
    """Reset one batch row of the stacked decode state to t=0.

    ``slot`` may be a traced int32 scalar: the continuous-batching engine
    jits this once and re-uses it for every admission.
    """
    from repro.models.quant_lstm import reset_recurrent_state_rows

    keys = _cell_state_keys(qlayers)
    out: Dict[str, Any] = {k: [] for k in keys}
    for i, (_, spec) in enumerate(qlayers):
        layer = tuple(states[k][i] for k in keys)
        for k, leaf in zip(keys, reset_recurrent_state_rows(spec, layer, slot)):
            out[k].append(leaf)
    length = states["len"]
    if length.ndim:
        length = length.at[slot].set(0)
    out["len"] = length
    return out


def write_quant_slot(states, slot, row_state):
    """Write a batch-1 state into batch row ``slot`` of a stacked state.

    The resume half of preemption: ``slice_state`` (plus a host copy) parks
    a stream's state in the pool, and this puts it back into whatever slot
    the scheduler picked -- bit-exactly, because every leaf is integer and
    row computations are batch-independent.  ``slot`` may be a traced int32
    scalar: the engine jits this once and reuses it for every resume.
    """
    out = {
        k: [leaf.at[slot].set(r[0])
            for leaf, r in zip(states[k], row_state[k])]
        for k in _quant_state_keys(states)
    }
    length = states["len"]
    if length.ndim:
        row_len = jnp.asarray(row_state["len"]).reshape(-1)[0]
        length = length.at[slot].set(row_len)
    out["len"] = length
    return out


def slice_state(states, row):
    """Extract one stream's decode state as a batch-1 state (bitwise view).

    Inverse of ``stack_state``; row computations are batch-independent, so
    slicing a slot out of a continuous-batching state and decoding it alone
    continues the stream bit-exactly.
    """
    sl = slice(row, row + 1)
    length = states["len"]
    out = {k: [leaf[sl] for leaf in states[k]]
           for k in _quant_state_keys(states)}
    out["len"] = length[sl] if length.ndim else length
    return out


def stack_state(state_list):
    """Concatenate per-stream decode states along the batch axis.

    Every state must come from the same ``qlayers``; scalar ``len`` entries
    are broadcast to one counter per stacked row.
    """
    keys = _quant_state_keys(state_list[0])
    n_layers = len(state_list[0][keys[0]])
    out = {
        k: [jnp.concatenate([s[k][i] for s in state_list], axis=0)
            for i in range(n_layers)]
        for k in keys
    }
    out["len"] = jnp.concatenate([
        s["len"] if s["len"].ndim else s["len"][None] for s in state_list])
    return out


def _quant_head(params, x):
    """LM head over float rows ``x (..., d)`` -> bf16 logits ``(..., V)``,
    evaluated in zero-padded blocks of exactly ``SUBLANES`` rows.

    A float matmul's rounding depends on its row count (the compiler tiles
    it by shape), so the same hidden row could give different logits -- and
    a different greedy token -- in the engine's S-row batch than in
    decode_single's one row.  Within one block shape, a row's logits depend
    on that row alone.
    """
    from repro.kernels.quant_lstm_scan import SUBLANES

    lead, d = x.shape[:-1], x.shape[-1]
    rows = x.reshape(-1, d).astype(jnp.bfloat16)
    n = rows.shape[0]
    n_pad = -(-n // SUBLANES) * SUBLANES
    blocks = jnp.pad(rows, ((0, n_pad - n), (0, 0))).reshape(
        -1, SUBLANES, d)
    # the barriers keep the compiler from slicing the padding back off
    # (a 1-row program would otherwise run a 1-row matmul again)
    bar = jax.lax.optimization_barrier
    logits = jax.lax.map(
        lambda b: bar(emb.logits_head(params, bar(b))), blocks)
    return logits.reshape(n_pad, -1)[:n].reshape(*lead, -1)


def _quant_stack(params, qlayers, tokens, states, backend, valid_len=None):
    """Run the integer recurrent stack over a ``(B, T)`` token block.

    Each layer quantizes its float input with its own calibrated (s_x, zp_x),
    runs the hoisted two-stage integer executor (``backend`` = xla | pallas |
    interpret) -- the layer's whole ``(B, T)`` input block goes through one
    time-batched packed GEMM before the recurrent scan / persistent Pallas
    sequence kernel -- and dequantizes for the next layer.  Returns the
    float stack output ``(B, T, d_out)`` plus the new per-layer states.

    ``valid_len`` (int32 ``(B,)``) selects the ragged masked executor: row b
    consumes only its first ``valid_len[b]`` tokens and freezes its
    per-layer state (and ``len`` counter) beyond that -- the chunked
    prefill path.  Outputs at positions ``>= valid_len[b]`` come from frozen
    state and must be ignored by the caller.
    """
    from repro.models import quant_lstm as QL

    keys = _cell_state_keys(qlayers)
    x = emb.embed_tokens(params, tokens).astype(jnp.float32)
    new_cols: Dict[str, list] = {k: [] for k in keys}
    for i, (arrays, spec) in enumerate(qlayers):
        x_q = QL.quantize_input(x, spec.s_x, spec.zp_x)
        ys_q, new_layer = QL.quant_recurrent_layer(
            arrays, spec, x_q, tuple(states[k][i] for k in keys),
            backend=backend, valid_len=valid_len)
        x = QL.dequantize_output(ys_q, spec.s_h, spec.zp_h_out)
        for k, leaf in zip(keys, new_layer):
            new_cols[k].append(leaf)
    advanced = tokens.shape[1] if valid_len is None else valid_len
    out: Dict[str, Any] = dict(new_cols)
    out["len"] = states["len"] + advanced
    return x, out


def quant_forward(params, qlayers, cfg: ArchConfig, tokens, states,
                  backend: str = "xla", valid_len=None):
    """Integer LSTM stack over ``tokens``: (B, T) -> logits (B, T, V).

    See ``_quant_stack`` for the layer pipeline and the ``valid_len``
    (ragged chunked-prefill) semantics.
    """
    x, new_states = _quant_stack(params, qlayers, tokens, states, backend,
                                 valid_len)
    return _quant_head(params, x), new_states


def quant_chunk_step(params, qlayers, cfg: ArchConfig, tokens, states,
                     valid_len, backend: str = "xla"):
    """Chunked-prefill step: ragged stack over a ``(B, K)`` block, LM head
    evaluated ONLY at each row's last valid position.

    The engine reads one next-token distribution per row, so running the
    vocab matmul over all K positions wastes (K-1)/K of the head compute --
    gather the ``(B, d_proj)`` last-valid hidden first, then project once.
    Rows with ``valid_len == 0`` gather position 0; their logits are
    garbage-by-construction and the caller ignores them (their state is
    frozen by the masked executor).  Returns ``((B, V) logits, new states)``.
    """
    x, new_states = _quant_stack(params, qlayers, tokens, states, backend,
                                 valid_len)
    idx = jnp.maximum(valid_len - 1, 0)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return _quant_head(params, last), new_states


def quant_verify_step(params, qlayers, cfg: ArchConfig, tokens, states,
                      valid_len, draft_len, backend: str = "xla"):
    """Speculative verify step: masked chunk forward with an all-positions
    head, in-graph acceptance, and per-row rollback to the accepted length.

    ``tokens`` is a ``(B, W)`` block where row b's first ``valid_len[b]``
    positions are real inputs: the leading ``valid_len[b] - draft_len[b]``
    are **committed** tokens (teacher-forced prompt tokens, or the fed-back
    last generated token) and the trailing ``draft_len[b]`` are **draft
    candidates** proposed by a drafter.  The step

    1. runs the ragged masked executor over the whole block ONCE from
       ``states`` and evaluates the LM head at every position (unlike
       ``quant_chunk_step``'s last-valid-only head: here each position's
       argmax is a verdict on the next draft),
    2. computes each row's **accepted length** in-graph: committed positions
       are always consumed; draft position j is consumed iff every earlier
       draft was and the model's argmax at position j-1 equals the draft
       token at j (greedy acceptance -- the draft IS what greedy decode
       would have fed),
    3. re-advances ``states`` with the masked executor to exactly the
       accepted length -- a chunk advance with per-row rollback, bit-equal
       to teacher-forcing each row's accepted prefix alone, because it IS
       that program.  State contributions of rejected positions never
       reach the committed state.

    Returns ``(pred, accepted, new_states)``: ``pred`` ``(B, W)`` int32 is
    the per-position greedy argmax (position j is the model's next token
    after consuming inputs ``0..j``; garbage for ``j >= accepted[b]``),
    ``accepted`` ``(B,)`` int32 is the number of inputs consumed
    (``valid_len - draft_len <= accepted <= valid_len``; 0 for idle rows).
    The caller emits ``pred[b, j]`` for each consumed generation position --
    up to ``draft_len + 1`` tokens per row per step, every one bit-identical
    to 1-token greedy decode by construction.
    """
    x, _ = _quant_stack(params, qlayers, tokens, states, backend, valid_len)
    pred = jnp.argmax(_quant_head(params, x), axis=-1).astype(jnp.int32)
    base = valid_len - draft_len
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    # draft position j matches iff the model's prediction after position
    # j-1 equals the draft fed at j (pos 0 is never a draft: base >= 1 for
    # every row that feeds anything)
    match = jnp.concatenate(
        [jnp.ones((tokens.shape[0], 1), bool), pred[:, :-1] == tokens[:, 1:]],
        axis=1)
    ok = (pos < base[:, None]) | ((pos < valid_len[:, None]) & match)
    accepted = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    _, new_states = _quant_stack(params, qlayers, tokens, states, backend,
                                 accepted)
    return pred, accepted, new_states


def quant_chunk_advance(params, qlayers, cfg: ArchConfig, tokens, states,
                        valid_len, backend: str = "xla"):
    """Chunked-prefill advance: ragged stack over ``(B, K)``, state only.

    For engine steps where NO slot finishes its prompt (and none is
    generating), the next-token distribution is never read -- skip the LM
    head entirely and return no logits, so consecutive prefill chunks can be
    dispatched back-to-back without a per-step device->host sync.  The state
    trajectory is identical to ``quant_chunk_step`` (the head reads state,
    never writes it).
    """
    _, new_states = _quant_stack(params, qlayers, tokens, states, backend,
                                 valid_len)
    return new_states


def quant_prefill(params, qlayers, cfg: ArchConfig, tokens, states,
                  backend: str = "xla"):
    """Teacher-forced integer prefill in ONE scanned pass over the prompt;
    the LM head runs only at the last position."""
    x, states = _quant_stack(params, qlayers, tokens, states, backend)
    return _quant_head(params, x[:, -1]), states


def quant_decode_step(params, qlayers, cfg: ArchConfig, token, states,
                      backend: str = "xla"):
    logits, states = quant_forward(params, qlayers, cfg, token, states,
                                   backend=backend)
    return logits[:, -1], states
