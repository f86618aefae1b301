"""Recurrent layers — lax.scan successors of the reference's RNN machinery.

Reference: ``/root/reference/paddle/gserver/layers/LstmLayer.cpp`` (LSTM with
peephole connections, reversed mode), ``GatedRecurrentLayer.cpp`` (GRU),
``RecurrentLayer.cpp`` (vanilla), and the ``SequenceToBatch`` batch-scheduling
trick (``SequenceToBatch.h``) that packs variable-length sequences for step-wise
kernels. On TPU the scheduling disappears: one ``lax.scan`` over the padded time
axis with per-step validity masks (state freezes past each sequence's end), and
optional segment-reset for packed rows. The gate matmuls are fused into one
``[D, 4H]`` projection so the MXU sees large GEMMs.

Step cells are exposed separately (``LSTMCell.step``) for the decoder-side
"recurrent group" pattern (the reference's ``LstmStepLayer``/``GruStepLayer``
used inside ``RecurrentGradientMachine`` unrolls).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import initializers as I
from ..core.module import Module
from . import activations
from .layers import Linear

__all__ = ["LSTMCell", "GRUCell", "SimpleRNNCell", "RNN", "BiRNN",
           "GatedShortConv"]


class LSTMCell(Module):
    """LSTM cell with optional peepholes (reference: ``LstmLayer.cpp`` — gates
    i,f,o with W_ic/W_fc/W_oc diagonal peephole weights; ``hl_lstm.h``)."""

    def __init__(self, hidden: int, use_peepholes: bool = True,
                 act="tanh", gate_act="sigmoid", name=None):
        super().__init__(name=name)
        self.hidden = hidden
        self.use_peepholes = use_peepholes
        self.act = activations.get(act)
        self.gate_act = activations.get(gate_act)

    def initial_state(self, batch: int):
        return (jnp.zeros((batch, self.hidden)),
                jnp.zeros((batch, self.hidden)))

    def step(self, state, x):
        with self.scope():
            return self._step(state, x)

    def input_proj(self, x):
        """Input-to-hidden half of the gates for a WHOLE sequence
        [..., T, D] in one MXU-shaped matmul — hoisted out of the scan by
        :class:`RNN` (pair with :meth:`step_proj`, which adds the serial
        hidden-to-hidden half). Declares every cell param in the same order
        as :meth:`step`, so init is identical whichever path runs first."""
        with self.scope():
            hd = self.hidden
            wx = self.param("wx", I.xavier_uniform, (x.shape[-1], 4 * hd))
            self.param("wh", I.orthogonal(), (hd, 4 * hd))
            b = self.param("b", I.zeros, (4 * hd,))
            return x @ wx + b

    def step_proj(self, state, zx):
        """One step from a precomputed input projection (see input_proj)."""
        with self.scope():
            h_prev, c_prev = state
            hd = self.hidden
            wh = self.param("wh", I.orthogonal(), (hd, 4 * hd))
            return self._gates(h_prev, c_prev, zx + h_prev @ wh)

    def _step(self, state, x):
        h_prev, c_prev = state
        hd = self.hidden
        wx = self.param("wx", I.xavier_uniform, (x.shape[-1], 4 * hd))
        wh = self.param("wh", I.orthogonal(), (hd, 4 * hd))
        b = self.param("b", I.zeros, (4 * hd,))
        return self._gates(h_prev, c_prev, x @ wx + h_prev @ wh + b)

    def _gates(self, h_prev, c_prev, z):
        hd = self.hidden
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        if self.use_peepholes:
            w_ic = self.param("w_ic", I.zeros, (hd,))
            w_fc = self.param("w_fc", I.zeros, (hd,))
            zi = zi + c_prev * w_ic
            zf = zf + c_prev * w_fc
        i = self.gate_act(zi)
        f = self.gate_act(zf)
        c = f * c_prev + i * self.act(zg)
        zo_ = zo
        if self.use_peepholes:
            w_oc = self.param("w_oc", I.zeros, (hd,))
            zo_ = zo + c * w_oc
        o = self.gate_act(zo_)
        h = o * self.act(c)
        return (h, c), h

    def forward(self, state, x):
        return self._step(state, x)


class GRUCell(Module):
    """GRU cell (reference: ``GatedRecurrentLayer.cpp``, ``hl_gpu_gru.cuh``)."""

    def __init__(self, hidden: int, act="tanh", gate_act="sigmoid", name=None):
        super().__init__(name=name)
        self.hidden = hidden
        self.act = activations.get(act)
        self.gate_act = activations.get(gate_act)

    def initial_state(self, batch: int):
        return jnp.zeros((batch, self.hidden))

    def step(self, state, x):
        with self.scope():
            return self._step(state, x)

    def input_proj(self, x):
        """Input half of the gates for a whole sequence (see
        ``LSTMCell.input_proj``); declares params in :meth:`step`'s order."""
        with self.scope():
            hd = self.hidden
            wx = self.param("wx", I.xavier_uniform, (x.shape[-1], 3 * hd))
            self.param("wh", I.orthogonal(), (hd, 2 * hd))
            self.param("wc", I.orthogonal(), (hd, hd))
            b = self.param("b", I.zeros, (3 * hd,))
            return x @ wx + b

    def step_proj(self, state, zx):
        with self.scope():
            hd = self.hidden
            wh = self.param("wh", I.orthogonal(), (hd, 2 * hd))
            wc = self.param("wc", I.orthogonal(), (hd, hd))
            return self._gates(state, zx, wh, wc)

    def _step(self, state, x):
        hd = self.hidden
        wx = self.param("wx", I.xavier_uniform, (x.shape[-1], 3 * hd))
        wh = self.param("wh", I.orthogonal(), (hd, 2 * hd))
        wc = self.param("wc", I.orthogonal(), (hd, hd))
        b = self.param("b", I.zeros, (3 * hd,))
        return self._gates(state, x @ wx + b, wh, wc)

    def _gates(self, h_prev, zx, wh, wc):
        zu, zr, zc = jnp.split(zx, 3, axis=-1)
        hu, hr = jnp.split(h_prev @ wh, 2, axis=-1)
        u = self.gate_act(zu + hu)
        r = self.gate_act(zr + hr)
        cand = self.act(zc + (r * h_prev) @ wc)
        h = u * h_prev + (1 - u) * cand
        return h, h

    def forward(self, state, x):
        return self._step(state, x)


class SimpleRNNCell(Module):
    """Vanilla RNN (reference: ``RecurrentLayer.cpp``)."""

    def __init__(self, hidden: int, act="tanh", name=None):
        super().__init__(name=name)
        self.hidden = hidden
        self.act = activations.get(act)

    def initial_state(self, batch: int):
        return jnp.zeros((batch, self.hidden))

    def step(self, state, x):
        with self.scope():
            return self._step(state, x)

    def input_proj(self, x):
        with self.scope():
            wx = self.param("wx", I.xavier_uniform,
                            (x.shape[-1], self.hidden))
            self.param("wh", I.orthogonal(), (self.hidden, self.hidden))
            b = self.param("b", I.zeros, (self.hidden,))
            return x @ wx + b

    def step_proj(self, state, zx):
        with self.scope():
            wh = self.param("wh", I.orthogonal(),
                            (self.hidden, self.hidden))
            h = self.act(zx + state @ wh)
            return h, h

    def _step(self, state, x):
        wx = self.param("wx", I.xavier_uniform, (x.shape[-1], self.hidden))
        wh = self.param("wh", I.orthogonal(), (self.hidden, self.hidden))
        b = self.param("b", I.zeros, (self.hidden,))
        h = self.act(x @ wx + state @ wh + b)
        return h, h

    def forward(self, state, x):
        return self._step(state, x)


class RNN(Module):
    """Run a cell over the time axis of ``x [B, T, D]`` with lax.scan.

    - ``mask [B, T]``: state freezes where mask==0 (padded steps) — replaces
      the reference's SequenceToBatch scheduling.
    - ``segment_starts [B, T]``: 1 where a new packed segment begins — state
      resets, enabling packed-row training (SURVEY.md §5).
    - ``reverse``: the reference's reversed-LSTM mode.
    - ``initial_state``: boot state (the RecurrentGradientMachine boot layer).
    Returns ``(outputs [B, T, H], final_state)``.
    """

    def __init__(self, cell, reverse: bool = False, unroll: int = 1,
                 name=None):
        super().__init__(name=name)
        self.cell = cell
        self.reverse = reverse
        # lax.scan unroll factor: an RNN step is a SMALL matmul, so the
        # while-loop iteration overhead (~10 us on TPU) can dominate;
        # unrolling amortizes it and lets XLA fuse across steps at the cost
        # of compile time (measured in PERF.md (older installation) "Round 5")
        self.unroll = unroll

    def forward(self, x, mask=None, segment_starts=None, initial_state=None):
        b, t = x.shape[0], x.shape[1]
        state0 = (initial_state if initial_state is not None
                  else self.cell.initial_state(b))

        # Materialize cell params once (outside scan) by tracing one step at
        # fixed path; scan then reuses them via closure.
        cell = self.cell

        # Input-projection hoist: cells exposing input_proj/step_proj get
        # their input-to-hidden gate matmul computed for the WHOLE sequence
        # in one MXU-shaped [B*T, D] @ [D, G] before the scan; only the
        # serial hidden-to-hidden half stays inside (halves LSTM scan FLOPs
        # — PERF.md (older installation) "Round 5").
        use_proj = hasattr(cell, "input_proj")
        if use_proj:
            x = cell.input_proj(x)
        cell_step = cell.step_proj if use_proj else cell.step

        def one_step(state, inputs):
            xt, mt, st = inputs
            if st is not None:
                # reset state where a new segment starts
                state = jax.tree_util.tree_map(
                    lambda s0, s: jnp.where(st[:, None] > 0, s0, s),
                    state0, state)
            new_state, out = cell_step(state, xt)
            if mt is not None:
                keep = mt[:, None]
                new_state = jax.tree_util.tree_map(
                    lambda n, o: keep * n + (1 - keep) * o, new_state, state)
                out = out * keep
            return new_state, out

        if self.reverse and segment_starts is not None:
            # The reversed scan enters each packed segment at its END, so the
            # reset flags must fire there: end[t] = start[t+1] (and the last
            # position always ends a segment), computed in original order and
            # reversed with the rest of the inputs below.
            segment_starts = jnp.concatenate(
                [segment_starts[:, 1:],
                 jnp.ones_like(segment_starts[:, :1])], axis=1)

        xs = jnp.swapaxes(x, 0, 1)                      # [T, B, D]
        ms = None if mask is None else jnp.swapaxes(mask, 0, 1)
        ss = None if segment_starts is None else jnp.swapaxes(segment_starts,
                                                              0, 1)
        if self.reverse:
            xs = xs[::-1]
            ms = None if ms is None else ms[::-1]
            ss = None if ss is None else ss[::-1]

        # Pre-create params: run one step eagerly so scan's trace finds them.
        _ = one_step(state0, (xs[0], None if ms is None else ms[0],
                              None if ss is None else ss[0]))

        def scan_body(state, inp):
            if ms is None and ss is None:
                xt = inp
                return one_step(state, (xt, None, None))
            if ss is None:
                xt, mt = inp
                return one_step(state, (xt, mt, None))
            if ms is None:
                xt, st = inp
                return one_step(state, (xt, None, st))
            xt, mt, st = inp
            return one_step(state, (xt, mt, st))

        if ms is None and ss is None:
            inputs = xs
        elif ss is None:
            inputs = (xs, ms)
        elif ms is None:
            inputs = (xs, ss)
        else:
            inputs = (xs, ms, ss)
        final, outs = lax.scan(scan_body, state0, inputs,
                               unroll=self.unroll)
        outs = jnp.swapaxes(outs, 0, 1)                 # [B, T, H]
        if self.reverse:
            outs = outs[:, ::-1]
        return outs, final


class BiRNN(Module):
    """Bidirectional wrapper (reference: ``networks.py bidirectional_lstm``):
    concat of forward and reverse passes with independent cells."""

    def __init__(self, fwd_cell, bwd_cell, unroll: int = 1, name=None):
        super().__init__(name=name)
        self.fwd = RNN(fwd_cell, reverse=False, unroll=unroll, name="fwd")
        self.bwd = RNN(bwd_cell, reverse=True, unroll=unroll, name="bwd")

    def forward(self, x, mask=None, segment_starts=None):
        of, _ = self.fwd(x, mask=mask, segment_starts=segment_starts)
        ob, _ = self.bwd(x, mask=mask, segment_starts=segment_starts)
        return jnp.concatenate([of, ob], axis=-1)


class MDLstm(Module):
    """Two-dimensional multi-directional LSTM over an image grid (reference:
    ``MDLstmLayer.cpp`` — Graves-style MDLSTM: each cell (i, j) receives
    recurrent input from its top (i-1, j) and left (i, j-1) neighbours, with
    one forget gate per direction).

    ``forward(x [B, H, W, D]) -> h [B, H, W, hidden]``. Implemented as a
    ``lax.scan`` over rows whose carry is the previous row's (h, c)
    [B, W, hidden], with an inner scan over columns carrying (h_left,
    c_left) — the same O(H*W) sequential dependency the recurrence itself
    has. Set ``reverse_h``/``reverse_w`` for the other three scan
    directions (the reference instantiates 4 directions for full MD-LSTM).
    """

    def __init__(self, hidden: int, act="tanh", gate_act="sigmoid",
                 reverse_h: bool = False, reverse_w: bool = False, name=None):
        super().__init__(name=name)
        self.hidden = hidden
        self.act = activations.get(act)
        self.gate_act = activations.get(gate_act)
        self.reverse_h = reverse_h
        self.reverse_w = reverse_w

    def forward(self, x):
        B, H, W, D = x.shape
        hd = self.hidden
        wx = self.param("wx", I.xavier_uniform, (D, 5 * hd))
        wh_up = self.param("wh_up", I.orthogonal(), (hd, 5 * hd))
        wh_left = self.param("wh_left", I.orthogonal(), (hd, 5 * hd))
        b = self.param("b", I.zeros, (5 * hd,))

        if self.reverse_h:
            x = x[:, ::-1]
        if self.reverse_w:
            x = x[:, :, ::-1]
        # precompute the input contribution for every cell in one matmul
        zx = jnp.einsum("bhwd,dk->bhwk", x, wx) + b

        def cell(h_up, c_up, h_left, c_left, z_in):
            z = z_in + h_up @ wh_up + h_left @ wh_left
            zi, zf1, zf2, zg, zo = jnp.split(z, 5, axis=-1)
            i = self.gate_act(zi)
            f_up = self.gate_act(zf1)
            f_left = self.gate_act(zf2)
            c = f_up * c_up + f_left * c_left + i * self.act(zg)
            h = self.gate_act(zo) * self.act(c)
            return h, c

        def row_step(carry_row, z_row):
            # carry_row: (h, c) of the row above, each [B, W, hd]
            h_above, c_above = carry_row

            def col_step(carry_col, inputs):
                h_left, c_left = carry_col
                z_in, h_up, c_up = inputs
                h, c = cell(h_up, c_up, h_left, c_left, z_in)
                return (h, c), (h, c)

            zeros = jnp.zeros((B, hd), zx.dtype)
            (_, _), (h_row, c_row) = jax.lax.scan(
                col_step, (zeros, zeros),
                (jnp.swapaxes(z_row, 0, 1),
                 jnp.swapaxes(h_above, 0, 1),
                 jnp.swapaxes(c_above, 0, 1)))
            h_row = jnp.swapaxes(h_row, 0, 1)     # [B, W, hd]
            c_row = jnp.swapaxes(c_row, 0, 1)
            return (h_row, c_row), h_row

        zeros_row = jnp.zeros((B, W, hd), zx.dtype)
        _, h_all = jax.lax.scan(row_step, (zeros_row, zeros_row),
                                jnp.swapaxes(zx, 0, 1))
        h = jnp.swapaxes(h_all, 0, 1)             # [B, H, W, hd]
        if self.reverse_h:
            h = h[:, ::-1]
        if self.reverse_w:
            h = h[:, :, ::-1]
        return h


class HierarchicalRNN(Module):
    """Two-level recurrence over nested sequences (reference: nested
    ``RecurrentGradientMachine`` — an outer recurrent group stepping over
    subsequences with an inner RNN per subsequence,
    ``gserver/gradientmachines/RecurrentGradientMachine.h:428``; equivalence
    fixture ``gserver/tests/sequence_nest_rnn.conf``).

    ``forward(data [B, S, T, D], sub_lengths [B, S], num_subseqs [B])``:
    the inner cell runs over each subsequence's tokens (state reset per
    subsequence — the nested frame boundary), its last state is the
    subsequence summary; the outer cell then runs over the S summaries.
    Returns ``(inner_out [B, S, T, Hi], outer_out [B, S, Ho])``. Inner runs
    batched over B*S (one scan, full MXU batch), outer over S.
    """

    def __init__(self, inner_cell, outer_cell, name=None):
        super().__init__(name=name)
        self.inner = RNN(inner_cell)
        self.outer = RNN(outer_cell)

    def forward(self, data, sub_lengths, num_subseqs):
        B, S, T = data.shape[:3]
        flat = data.reshape((B * S, T) + data.shape[3:])
        flat_len = sub_lengths.reshape(B * S)
        from ..core.sequence import length_mask
        inner_out, _ = self.inner(flat, mask=length_mask(flat_len, T))
        inner_out = inner_out.reshape((B, S, T) + inner_out.shape[2:])
        # subsequence summary = last valid inner state
        from .sequence_ops import sub_seq_last
        summaries = sub_seq_last(inner_out, sub_lengths)     # [B, S, Hi]
        outer_out, _ = self.outer(summaries,
                                  mask=length_mask(num_subseqs, S))
        return inner_out, outer_out


class GatedShortConv(Module):
    """The gated short convolution of Liquid AI's LFM2 blocks, a token
    mixer with a state of ``kernel - 1`` rows (``conv_L_cache``), no
    attention and no biases: ``x [B, T, D] -> [B, T, D]``,

    - ``B, C, x~ = split3(x W_in)``, ``W_in [D, 3D]``;
    - ``u = B * x~``;
    - ``v_t = sum_j w_j * u_{t - kernel + 1 + j}``, a depthwise causal
      convolution (``w [kernel, D]``, parameter ``conv``; rows before the
      first are zeros);
    - ``(C * v) W_out``.

    Three forms, one per entry point of a served model: :meth:`forward`
    over whole sequences; :meth:`decode`, one token a slot against the
    slot's state; :meth:`decode_span`, ``Q`` consecutive tokens a slot
    that start from the state and leave it as it is after their last LIVE
    row. The state is a STATE group's pool (``serve/kv_cache.py``):
    ``[L, max_slots, (kernel - 1) * D]``, a slot's ``kernel - 1`` rows
    side by side in one row (a layer's state is then a ``[slots, 2D]``
    array the TPU tiles whole, and a chunk's write is one row of it; a
    ``[.., kernel - 1, D]`` pool puts its 2 rows where the tile wants 8
    and XLA re-lays it round a program), this layer ``layer``, the call's
    ``S`` rows the consecutive slots ``first .. first + S - 1`` (``first``
    a scalar), read and written in place with one ``dynamic_slice`` and
    one ``dynamic_update_slice``. Against
    a state, ``u`` enters the convolution rounded as the state holds it,
    in the span as in the tick, so a prompt gives the same outputs however
    it is cut into chunks; a span that starts at position 0 starts from
    zeros whatever the pool holds (an admission's reset, inside the
    program that writes the state), and an inactive slot's state is left
    as it was."""

    def __init__(self, dim: int, kernel: int = 3, w_init=I.fan_in_uniform,
                 name=None):
        super().__init__(name=name)
        assert kernel >= 2, kernel
        self.dim, self.kernel = dim, kernel
        self.w_init = w_init
        self.in_proj = Linear(3 * dim, use_bias=False, w_init=w_init)
        self.out_proj = Linear(dim, use_bias=False, w_init=w_init)

    @property
    def state_row(self) -> Tuple[int]:
        """One slot's state in one layer: ``((kernel - 1) * dim,)``."""
        return ((self.kernel - 1) * self.dim,)

    def _gated_input(self, x):
        """``(u, C)`` of ``x``, float32."""
        b, c, xt = jnp.split(self.in_proj(x).astype(jnp.float32), 3, -1)
        return b * xt, c

    def _mix(self, rows, c):
        """``rows [B, kernel - 1 + T, D]``: the ``kernel - 1`` rows before
        the first output and the ``T`` outputs' own ``u``. Returns the
        block's output ``[B, T, D]``."""
        T = rows.shape[1] - self.kernel + 1
        w = self.param("conv", self.w_init, (self.kernel, self.dim))
        rows, w = rows.astype(jnp.float32), w.astype(jnp.float32)
        v = sum(w[j] * rows[:, j:j + T] for j in range(self.kernel))
        return self.out_proj(c * v)

    def forward(self, x):
        with self.scope():
            u, c = self._gated_input(x)
            return self._mix(jnp.pad(u, ((0, 0), (self.kernel - 1, 0),
                                         (0, 0))), c)

    def _state_rows(self, state, layer, first, S):
        """Slots ``first .. first + S - 1``'s rows ``[S, kernel - 1, D]``."""
        rows = lax.dynamic_slice(state, (layer, first, 0),
                                 (1, S, state.shape[-1]))
        return rows.reshape(S, self.kernel - 1, self.dim)

    def _put(self, state, layer, first, rows):
        rows = rows.astype(state.dtype).reshape(1, rows.shape[0], -1)
        return lax.dynamic_update_slice(state, rows, (layer, first, 0))

    def decode(self, x, state, layer, first, active):
        """One new token a slot: ``x [S, 1, D]`` for the slots ``first ..
        first + S - 1``, ``active [S]``. Returns ``(out [S, 1, D],
        state)``."""
        with self.scope():
            S = x.shape[0]
            u, c = self._gated_input(x)
            old = self._state_rows(state, layer, first, S)
            rows = jnp.concatenate([old, u.astype(state.dtype)], axis=1)
            out = self._mix(rows, c)
            new = jnp.where(active[:, None, None], rows[:, 1:], old)
            return out, self._put(state, layer, first, new)

    def decode_span(self, x, state, layer, first, start, n, active):
        """``Q`` consecutive new tokens a slot (a prefill chunk): ``x [S,
        Q, D]`` for the slots ``first .. first + S - 1``, at positions
        ``start[s] + j``, ``n[s]`` of them live.
        Returns ``(out [S, Q, D], state)``: the state after the slot's
        last live row (unchanged where ``n`` is 0 or the slot inactive;
        padding rows never enter it)."""
        with self.scope():
            S = x.shape[0]
            u, c = self._gated_input(x)
            held = self._state_rows(state, layer, first, S)
            old = jnp.where((start > 0)[:, None, None], held, 0)
            rows = jnp.concatenate([old, u.astype(state.dtype)], axis=1)
            out = self._mix(rows, c)
            # rows n .. n + kernel - 2 of ``rows`` end at the last live u
            new = jax.vmap(lambda r, m: lax.dynamic_slice_in_dim(
                r, m, self.kernel - 1, axis=0))(rows, n)
            new = jnp.where((active & (n > 0))[:, None, None], new, held)
            return out, self._put(state, layer, first, new)
