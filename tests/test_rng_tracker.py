"""TP dropout RNG tracker: per-rank streams
via meta_parallel.model_parallel_random_seed +
get_rng_state_tracker().rng_state(), eager and jit."""

import numpy as np
import pytest

import paddle1_tpu as paddle
import paddle1_tpu.nn.functional as F
from paddle1_tpu.core.generator import (rng_scope, get_rng_tracker,
                                        MODEL_PARALLEL_RNG)
from paddle1_tpu.core.tensor import to_tensor
from paddle1_tpu.distributed.meta_parallel import (
    get_rng_state_tracker, model_parallel_random_seed)


class _FakeHcg:
    def __init__(self, rank):
        self._r = rank

    def get_model_parallel_rank(self):
        return self._r


def _mask(x):
    out = np.asarray(F.dropout(to_tensor(x), p=0.5,
                               training=True).numpy())
    return out != 0


def _seed_as_rank(monkeypatch, rank, seed=2048):
    from paddle1_tpu.distributed import topology
    monkeypatch.setattr(topology, "get_hybrid_communicate_group",
                        lambda: _FakeHcg(rank))
    model_parallel_random_seed(seed)


class TestEagerStreams:
    def test_mp_ranks_draw_distinct_masks_in_tracked_region(
            self, monkeypatch):
        x = np.ones((64, 64), np.float32)
        tr = get_rng_state_tracker()
        _seed_as_rank(monkeypatch, 0)
        with tr.rng_state(MODEL_PARALLEL_RNG):
            m0 = _mask(x)
        _seed_as_rank(monkeypatch, 1)
        with tr.rng_state(MODEL_PARALLEL_RNG):
            m1 = _mask(x)
        assert (m0 != m1).any()

    def test_replicated_stream_identical_across_ranks(
            self, monkeypatch):
        x = np.ones((64, 64), np.float32)
        _seed_as_rank(monkeypatch, 0)
        a = _mask(x)
        _seed_as_rank(monkeypatch, 1)
        b = _mask(x)
        np.testing.assert_array_equal(a, b)

    def test_tracked_region_restores_default_stream(self, monkeypatch):
        x = np.ones((32, 32), np.float32)
        _seed_as_rank(monkeypatch, 0)
        ref = _mask(x)
        _seed_as_rank(monkeypatch, 0)
        with get_rng_state_tracker().rng_state():
            _mask(x)  # consumes the TRACKED stream only
        after = _mask(x)
        np.testing.assert_array_equal(ref, after)

    def test_duplicate_seed_rejected(self):
        tr = get_rng_tracker()
        tr.reset()
        tr.add("a", 7)
        with pytest.raises(Exception, match="already"):
            tr.add("b", 7)
        with pytest.raises(Exception, match="already"):
            tr.add("a", 8)
        tr.reset()

    def test_unknown_state_teaches(self):
        tr = get_rng_tracker()
        tr.reset()
        with pytest.raises(Exception, match="add"):
            with tr.rng_state("never_added"):
                pass


class TestJitPath:
    def test_scope_reproducible_and_per_name_distinct(
            self, monkeypatch):
        import jax
        x = np.ones((64, 64), np.float32)
        _seed_as_rank(monkeypatch, 0)
        tr = get_rng_state_tracker()
        key = jax.random.key(5)

        def tracked_mask():
            with tr.rng_state(MODEL_PARALLEL_RNG):
                return _mask(x)
        with rng_scope(key):
            a = tracked_mask()
        with rng_scope(key):
            b = tracked_mask()
        np.testing.assert_array_equal(a, b)  # deterministic in the key
        with rng_scope(key):
            plain = _mask(x)
        assert (a != plain).any()            # tracked != default stream

    def test_repeated_regions_draw_distinct_masks(self, monkeypatch):
        """The per-layer dropout pattern: two tracked regions in one
        trace must NOT restart the same stream."""
        import jax
        x = np.ones((64, 64), np.float32)
        _seed_as_rank(monkeypatch, 0)
        tr = get_rng_state_tracker()
        key = jax.random.key(21)
        with rng_scope(key):
            with tr.rng_state(MODEL_PARALLEL_RNG):
                m1 = _mask(x)
            with tr.rng_state(MODEL_PARALLEL_RNG):
                m2 = _mask(x)
        assert (m1 != m2).any()
        # and the pair is still reproducible under the same key
        with rng_scope(key):
            with tr.rng_state(MODEL_PARALLEL_RNG):
                n1 = _mask(x)
            with tr.rng_state(MODEL_PARALLEL_RNG):
                n2 = _mask(x)
        np.testing.assert_array_equal(m1, n1)
        np.testing.assert_array_equal(m2, n2)

    def test_scope_ranks_differ(self, monkeypatch):
        import jax
        x = np.ones((64, 64), np.float32)
        key = jax.random.key(9)
        tr = get_rng_state_tracker()
        _seed_as_rank(monkeypatch, 0)
        with rng_scope(key):
            with tr.rng_state(MODEL_PARALLEL_RNG):
                m0 = _mask(x)
        _seed_as_rank(monkeypatch, 1)
        with rng_scope(key):
            with tr.rng_state(MODEL_PARALLEL_RNG):
                m1 = _mask(x)
        assert (m0 != m1).any()
