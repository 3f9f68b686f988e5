"""Seq2seq decode stack: dynamic_decode +
BeamSearchDecoder + BasicDecoder/helpers vs numpy references.

Reference: /root/reference/python/paddle/fluid/layers/rnn.py
(Decoder:753, BeamSearchDecoder:866, dynamic_decode:1581,
helpers:1673-2127)."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.nn as nn
from paddle1_tpu.core.tensor import to_tensor

B, H, V, EMB = 3, 8, 11, 6
START, END = 1, 2


def _np(t):
    return np.asarray(t.numpy())


class _Seq2SeqFixture:
    """A tiny decoder: GRU cell + embedding + vocab projection with
    fixed weights, plus a pure-numpy twin of the step function."""

    def __init__(self, seed=0):
        rng = np.random.default_rng(seed)
        self.emb_w = rng.standard_normal((V, EMB)).astype(np.float32)
        self.proj_w = rng.standard_normal((H, V)).astype(np.float32) * 2.0
        self.proj_b = rng.standard_normal(V).astype(np.float32)
        self.cell = nn.GRUCell(EMB, H)
        # freeze cell weights to known values
        self.wi = rng.standard_normal((3 * H, EMB)).astype(np.float32) * 0.5
        self.wh = rng.standard_normal((3 * H, H)).astype(np.float32) * 0.5
        self.bi = rng.standard_normal(3 * H).astype(np.float32) * 0.1
        self.bh = rng.standard_normal(3 * H).astype(np.float32) * 0.1
        self.cell.weight_ih.set_value(self.wi)
        self.cell.weight_hh.set_value(self.wh)
        self.cell.bias_ih.set_value(self.bi)
        self.cell.bias_hh.set_value(self.bh)
        self.h0 = rng.standard_normal((B, H)).astype(np.float32)

    def embedding_fn(self, ids):
        w = to_tensor(self.emb_w)
        import paddle1_tpu.nn.functional as F
        return F.embedding(ids, w)

    def output_fn(self, h):
        return paddle.matmul(h, to_tensor(self.proj_w)) \
            + to_tensor(self.proj_b)

    # -- numpy twin --
    def np_step(self, x, h):
        def sigmoid(v):
            return 1.0 / (1.0 + np.exp(-v))
        xg = x @ self.wi.T + self.bi
        hg = h @ self.wh.T + self.bh
        xr, xz, xn = np.split(xg, 3, axis=-1)
        hr, hz, hn = np.split(hg, 3, axis=-1)
        r, z = sigmoid(xr + hr), sigmoid(xz + hz)
        n = np.tanh(xn + r * hn)
        return (1 - z) * n + z * h

    def np_logits(self, h):
        return h @ self.proj_w + self.proj_b


def _np_log_softmax(x):
    m = x - x.max(axis=-1, keepdims=True)
    return m - np.log(np.exp(m).sum(axis=-1, keepdims=True))


def _np_greedy_decode(fx, max_steps):
    """Numpy greedy decode loop (GreedyEmbeddingHelper semantics)."""
    h = fx.h0.copy()
    ids = np.full(B, START, np.int64)
    finished = np.zeros(B, bool)
    all_ids, lengths = [], np.zeros(B, np.int64)
    for _ in range(max_steps + 1):
        if finished.all():
            break
        x = fx.emb_w[ids]
        h = fx.np_step(x, h)
        samp = fx.np_logits(h).argmax(-1).astype(np.int64)
        all_ids.append(samp)
        lengths += (~finished).astype(np.int64)
        finished = finished | (samp == END)
        ids = samp
    return np.stack(all_ids, axis=1), lengths


class TestGreedyDecode:
    def test_matches_numpy(self):
        fx = _Seq2SeqFixture()
        helper = nn.GreedyEmbeddingHelper(
            fx.embedding_fn, np.full(B, START, np.int64), END)
        dec = nn.BasicDecoder(fx.cell, helper, output_fn=fx.output_fn)
        outs, final_states, lens = nn.dynamic_decode(
            dec, inits=to_tensor(fx.h0), max_step_num=15,
            return_length=True)
        ref_ids, ref_lens = _np_greedy_decode(fx, 15)
        got = _np(outs.sample_ids)
        assert got.shape[0] == B
        # compare up to each row's decode length (positions past
        # finished keep sampling in both implementations)
        np.testing.assert_array_equal(got[:, :ref_ids.shape[1]], ref_ids)
        np.testing.assert_array_equal(_np(lens), ref_lens)

    def test_cell_outputs_match_states(self):
        fx = _Seq2SeqFixture(seed=5)
        helper = nn.GreedyEmbeddingHelper(
            fx.embedding_fn, np.full(B, START, np.int64), END)
        dec = nn.BasicDecoder(fx.cell, helper, output_fn=fx.output_fn)
        outs, final_states = nn.dynamic_decode(
            dec, inits=to_tensor(fx.h0), max_step_num=4)
        # logits at step 0 = proj(np_step(emb[START], h0))
        h1 = fx.np_step(fx.emb_w[np.full(B, START)], fx.h0)
        np.testing.assert_allclose(_np(outs.cell_outputs)[:, 0],
                                   fx.np_logits(h1), rtol=2e-4,
                                   atol=2e-4)


class TestSampleDecode:
    def test_temperature_and_reproducible_seed(self):
        fx = _Seq2SeqFixture(seed=2)

        def run(seed):
            helper = nn.SampleEmbeddingHelper(
                fx.embedding_fn, np.full(B, START, np.int64), END,
                softmax_temperature=0.7, seed=seed)
            dec = nn.BasicDecoder(fx.cell, helper,
                                  output_fn=fx.output_fn)
            outs, _ = nn.dynamic_decode(dec, inits=to_tensor(fx.h0),
                                        max_step_num=6)
            return _np(outs.sample_ids)
        a, b2 = run(seed=7), run(seed=7)
        np.testing.assert_array_equal(a, b2)
        assert a.min() >= 0 and a.max() < V


class TestTrainingHelper:
    def test_teacher_forcing_matches_rnn(self):
        fx = _Seq2SeqFixture(seed=3)
        rng = np.random.default_rng(4)
        T = 5
        gt = rng.standard_normal((B, T, EMB)).astype(np.float32)
        seq_len = np.array([5, 3, 4], np.int64)
        helper = nn.TrainingHelper(to_tensor(gt), seq_len)
        dec = nn.BasicDecoder(fx.cell, helper, output_fn=fx.output_fn)
        outs, _, lens = nn.dynamic_decode(dec, inits=to_tensor(fx.h0),
                                          return_length=True)
        # numpy: run the cell over ground-truth inputs
        h = fx.h0.copy()
        ref = []
        for t in range(int(seq_len.max())):
            h = fx.np_step(gt[:, t], h)
            ref.append(fx.np_logits(h))
        ref = np.stack(ref, axis=1)
        got = _np(outs.cell_outputs)
        assert got.shape[1] == int(seq_len.max())
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(_np(lens), seq_len)

    def test_gradients_flow_to_cell(self):
        fx = _Seq2SeqFixture(seed=6)
        gt = np.random.default_rng(1).standard_normal(
            (B, 4, EMB)).astype(np.float32)
        helper = nn.TrainingHelper(to_tensor(gt), np.full(B, 4, np.int64))
        dec = nn.BasicDecoder(fx.cell, helper, output_fn=fx.output_fn)
        outs, _ = nn.dynamic_decode(dec, inits=to_tensor(fx.h0))
        loss = (outs.cell_outputs ** 2).mean()
        loss.backward()
        g = _np(fx.cell.weight_ih.grad)
        assert np.abs(g).sum() > 0


def _np_beam_decode(fx, beam_size, max_steps):
    """Independent numpy beam search (batch loop, per-beam lists)."""
    K = beam_size
    results = []
    for b in range(B):
        h = np.repeat(fx.h0[b:b + 1], K, axis=0)  # [K, H]
        log_probs = np.array([0.0] + [-1e9] * (K - 1), np.float32)
        tokens = np.full(K, START, np.int64)
        finished = np.zeros(K, bool)
        lengths = np.zeros(K, np.int64)
        step_tokens, step_parents = [], []
        for _ in range(max_steps + 1):
            if finished.all():
                break
            x = fx.emb_w[tokens]
            h_new = fx.np_step(x, h)
            step_lp = _np_log_softmax(fx.np_logits(h_new))  # [K, V]
            noend = np.full(V, -1e9, np.float32)
            noend[END] = 0.0
            step_lp = np.where(finished[:, None], noend[None], step_lp)
            scores = (log_probs[:, None] + step_lp).reshape(-1)
            top = np.argsort(-scores, kind="stable")[:K]
            parents, toks = top // V, (top % V).astype(np.int64)
            log_probs = scores[top]
            finished_new = finished[parents] | (toks == END)
            lengths = lengths[parents] + (~finished[parents]).astype(
                np.int64)
            h = h_new[parents]
            finished = finished_new
            tokens = toks
            step_tokens.append(toks)
            step_parents.append(parents)
        # gather_tree back-trace
        Tn = len(step_tokens)
        seqs = np.zeros((Tn, K), np.int64)
        beam = np.arange(K)
        for t in range(Tn - 1, -1, -1):
            seqs[t] = step_tokens[t][beam]
            beam = step_parents[t][beam]
        results.append((seqs, log_probs, lengths))
    return results


class TestBeamSearchDecode:
    def test_matches_numpy_beam_search(self):
        fx = _Seq2SeqFixture(seed=8)
        K = 4
        dec = nn.BeamSearchDecoder(fx.cell, START, END, K,
                                   embedding_fn=fx.embedding_fn,
                                   output_fn=fx.output_fn)
        ids, final_states, lens = nn.dynamic_decode(
            dec, inits=to_tensor(fx.h0), max_step_num=12,
            output_time_major=True, return_length=True)
        got_ids = _np(ids)            # [T, B, K]
        got_scores = _np(final_states.log_probs)
        got_lens = _np(lens)
        ref = _np_beam_decode(fx, K, 12)
        for b in range(B):
            seqs, scores, lengths = ref[b]
            Tn = seqs.shape[0]
            np.testing.assert_array_equal(got_ids[:Tn, b], seqs)
            np.testing.assert_allclose(got_scores[b], scores,
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_array_equal(got_lens[b], lengths)

    def test_beam1_equals_greedy(self):
        fx = _Seq2SeqFixture(seed=9)
        dec = nn.BeamSearchDecoder(fx.cell, START, END, 1,
                                   embedding_fn=fx.embedding_fn,
                                   output_fn=fx.output_fn)
        ids, _ = nn.dynamic_decode(dec, inits=to_tensor(fx.h0),
                                   max_step_num=10)
        ref_ids, ref_lens = _np_greedy_decode(fx, 10)
        got = _np(ids)[:, :, 0]       # [B, T]
        for b in range(B):
            L = int(ref_lens[b])
            np.testing.assert_array_equal(got[b, :L], ref_ids[b, :L])

    def test_batch_major_default_and_tile_helper(self):
        fx = _Seq2SeqFixture(seed=10)
        K = 3
        dec = nn.BeamSearchDecoder(fx.cell, START, END, K,
                                   embedding_fn=fx.embedding_fn,
                                   output_fn=fx.output_fn)
        ids, _ = nn.dynamic_decode(dec, inits=to_tensor(fx.h0),
                                   max_step_num=5)
        assert _np(ids).shape[0] == B and _np(ids).shape[2] == K
        enc = to_tensor(np.arange(B * 2, dtype=np.float32).reshape(B, 2))
        tiled = nn.BeamSearchDecoder.tile_beam_merge_with_batch(enc, K)
        tn = _np(tiled)
        assert tn.shape == (B * K, 2)
        np.testing.assert_array_equal(tn[:K], np.repeat(_np(enc)[:1], K,
                                                        axis=0))

    def test_finished_beams_emit_end_fill(self):
        """After a beam finishes, back-traced positions keep sampling
        end tokens: every position at/after the first END is END."""
        fx = _Seq2SeqFixture(seed=11)
        dec = nn.BeamSearchDecoder(fx.cell, START, END, 4,
                                   embedding_fn=fx.embedding_fn,
                                   output_fn=fx.output_fn)
        ids, st, lens = nn.dynamic_decode(
            dec, inits=to_tensor(fx.h0), max_step_num=12,
            output_time_major=True, return_length=True)
        got, ln = _np(ids), _np(lens)
        fin = _np(st.finished)
        for b in range(B):
            for k in range(4):
                if fin[b, k]:
                    seq = got[:, b, k]
                    ends = np.where(seq == END)[0]
                    assert ends.size, seq
                    assert (seq[ends[0]:] == END).all()
                    assert ln[b, k] >= 1


class TestDecodeAccumulationLinear:
    """ISSUE 9 satellite: dynamic_decode's output accumulation must be
    O(steps) — per-step outputs buffered in a host list, ONE stack at
    finalize — never re-concatenated per step (O(steps²) copy work and
    a growing-shape retrace per step). Pinned by an op-count regression
    plus a bit-parity check against the per-step-concat formulation."""

    def _run(self, T, fx=None):
        fx = fx or _Seq2SeqFixture(seed=12)
        gt = np.random.default_rng(7).standard_normal(
            (B, T, EMB)).astype(np.float32)
        helper = nn.TrainingHelper(to_tensor(gt),
                                   np.full(B, T, np.int64))
        dec = nn.BasicDecoder(fx.cell, helper, output_fn=fx.output_fn)
        return nn.dynamic_decode(dec, inits=to_tensor(fx.h0))

    def test_stack_once_and_no_per_step_concat(self, monkeypatch):
        from paddle1_tpu.ops import manip_ops
        counts = {"stack": 0, "concat": 0}
        real_stack, real_concat = manip_ops.stack, manip_ops.concat

        def stack(x, axis=0, name=None):
            counts["stack"] += 1
            return real_stack(x, axis=axis)

        def concat(x, axis=0, name=None):
            counts["concat"] += 1
            return real_concat(x, axis=axis)
        import paddle1_tpu.nn.decode as D
        monkeypatch.setattr(D.manip_ops, "stack", stack)
        monkeypatch.setattr(D.manip_ops, "concat", concat)
        per_T = {}
        for T in (4, 8):
            counts["stack"] = counts["concat"] = 0
            self._run(T)
            per_T[T] = dict(counts)
        # one stack per OUTPUT LEAF (cell_outputs + sample_ids), no
        # driver-side concats — and neither grows with the step count
        assert per_T[4]["stack"] == per_T[8]["stack"] == 2
        assert per_T[4]["concat"] == per_T[8]["concat"] == 0

    def test_parity_with_per_step_concat_accumulation(self, monkeypatch):
        """The finalize-time single stack must be BIT-identical to the
        O(steps²) formulation it replaces (re-concatenating the
        accumulator every step)."""
        fx = _Seq2SeqFixture(seed=12)
        outs, _ = self._run(6, fx)
        ref = _np(outs.cell_outputs)

        from paddle1_tpu.ops import manip_ops
        real_stack = manip_ops.stack

        def stack_via_per_step_concat(x, axis=0, name=None):
            from paddle1_tpu.ops.manip_ops import concat, unsqueeze
            acc = unsqueeze(x[0], axis)
            for t in x[1:]:  # the quadratic re-concat, on purpose
                acc = concat([acc, unsqueeze(t, axis)], axis=axis)
            return acc
        import paddle1_tpu.nn.decode as D
        monkeypatch.setattr(D.manip_ops, "stack",
                            stack_via_per_step_concat)
        outs2, _ = self._run(6, fx)
        monkeypatch.setattr(D.manip_ops, "stack", real_stack)
        np.testing.assert_array_equal(ref, _np(outs2.cell_outputs))

    def test_repeat_runs_bit_identical(self):
        fx = _Seq2SeqFixture(seed=12)
        a, _ = self._run(5, fx)
        b, _ = self._run(5, fx)
        np.testing.assert_array_equal(_np(a.cell_outputs),
                                      _np(b.cell_outputs))
        np.testing.assert_array_equal(_np(a.sample_ids),
                                      _np(b.sample_ids))


class TestFluidSpellings:
    def test_names_resolve(self):
        import paddle1_tpu.fluid.layers as L
        for n in ("dynamic_decode", "BeamSearchDecoder", "BasicDecoder",
                  "TrainingHelper", "GreedyEmbeddingHelper",
                  "SampleEmbeddingHelper", "DecodeHelper", "Decoder"):
            assert getattr(L, n) is getattr(nn, n)
