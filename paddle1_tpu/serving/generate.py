"""Generative serving: device-resident KV-cache decode with slot-based
continuous batching and per-token streaming (ISSUE 9 / ROADMAP item 2).

The eager decode stack (``nn.decode.dynamic_decode``) pays one host
round trip — and, through the concat-based
``MultiHeadAttention.Cache``, one growing-shape retrace — per token per
sequence. This module is the serving analog of PR 1's ``step_many``:
the whole autoregressive loop stays on device and ONE jitted dispatch
per token advances every active sequence, however many there are and
whenever each arrived.

Design (Orca's iteration-level continuous batching + vLLM's
preallocated KV management, adapted to a bucketed-XLA world where
shapes must stay static):

* **Prefill/decode split.** Prefill — the whole prompt in one causal
  pass — is compiled once per *prompt-length bucket*
  (``serve_gen_prefill_buckets``, resolved through the same
  ``resolve_buckets`` policy as the batch buckets). Decode is compiled
  exactly ONCE: its signature is pinned to the fixed
  ``[slots, max_seq]`` cache, so ragged arrivals, ragged prompt
  lengths, and any active-slot pattern reuse the same executable (the
  ``decode_compile_count`` trace counter is the acceptance gate).
* **Device-resident slot cache.** Per layer, preallocated
  ``[slots, max_seq, heads, dim]`` K/V arrays
  (:meth:`~paddle1_tpu.nn.MultiHeadAttention.gen_slot_cache`) written
  in place at a per-slot cursor via ``dynamic_update_slice`` and
  DONATED through every dispatch — no per-token cache copy, no
  per-token reshape, no retrace.
* **Slot-based continuous batching.** New requests claim free slots in
  the running decode batch between steps, as finished ones release
  theirs; a slot's rows are never read by any other slot (per-row
  writes + per-slot causal masks), so cohabiting sequences are
  bit-identical to an uncontended run — the isolation contract the
  ``gen_slot_wedge`` chaos test pins.
* **Sampling on device.** Greedy/temperature/top-k (the shared
  ``nn.decode.sample_logits_array`` op) run *inside* the jitted step
  with per-slot RNG keys (carried as raw key data, split per token),
  so sampled decode is still one dispatch and a request's draws depend
  only on (its seed, its token index) — never on its slot or its
  neighbors.
* **Per-token streaming.** Each request gets a :class:`TokenStream`
  (iterator + ``cancel()``); a bounded per-stream buffer is the
  backpressure (the ``core/async_loss`` bounded-window idiom): a
  client that stops consuming parks its slot instead of growing host
  memory. Admission/deadline/shed/drain follow the PR 4 Server
  contracts, with the accounting extended to tokens:
  ``tokens_generated == tokens_streamed + tokens_dropped`` and
  request-level ``unaccounted == 0`` in every drain report.

Quickstart::

    lm = CausalLM(vocab_size=32000, d_model=512, nhead=8,
                  num_layers=12, max_seq=512)
    srv = GenerationServer(lm, slots=16, max_seq=512, eos_id=2).start()
    stream = srv.submit(prompt_ids, max_new_tokens=128, temperature=0.8,
                        top_k=40, seed=7)
    for tok in stream:          # per-token, as they decode
        print(tok)
    srv.drain()                 # unaccounted == 0, tokens_owed == 0
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core import chaos as core_chaos
from ..core import flags as core_flags
from ..core import health as core_health
from ..core import jit_sanitizer
from ..core import locks
from ..core.errors import InvalidArgumentError
from .engine import resolve_buckets
from ..obs import events as obs_events
from .errors import (DeadlineExceeded, KVPoolExhausted, ServerClosed,
                     ServerOverloaded, SlotWedged, StreamCancelled)
from .metrics import ServingMetrics
from .paging import PARKING_PAGE, PagePool
from .speculate import NGramSpeculator

__all__ = ["CausalLM", "GenerationEngine", "GenerationServer",
           "TokenStream"]


# ---------------------------------------------------------------------------
# reference model

from ..nn.layer_base import Layer as _Layer  # noqa: E402  (nn loads
# before serving in the package __init__, and nn never imports serving)


class CausalLM(_Layer):
    """Small decoder-only transformer LM built from the repo's own
    blocks — the generation engine's reference model (tests/bench serve
    it; users serve any Layer implementing the same contract:
    ``gen_slot_cache(slots, max_seq)`` plus
    ``forward(ids, cache=, positions=, attn_mask=)`` returning
    ``(logits, new_cache)`` when a cache is passed).

    Supports BOTH cache disciplines: the serving
    :attr:`~paddle1_tpu.nn.MultiHeadAttention.GenCache` slot path and
    the eager concat-based ``Cache`` path (``empty_cache``), so the
    same weights drive the engine and the ``dynamic_decode`` baseline.
    """

    def __init__(self, vocab_size, d_model=64, nhead=4,
                 dim_feedforward=128, num_layers=2, max_seq=256):
        super().__init__()
        from .. import nn
        self.vocab_size = int(vocab_size)
        self.max_seq = int(max_seq)
        self.embed = nn.Embedding(self.vocab_size, d_model)
        self.pos_embed = nn.Embedding(self.max_seq, d_model)
        layer = nn.TransformerEncoderLayer(
            d_model, nhead, dim_feedforward, dropout=0.0)
        self.encoder = nn.TransformerEncoder(layer, num_layers)
        self.head = nn.Linear(d_model, self.vocab_size)

    def gen_slot_cache(self, slots, max_seq, dtype="float32"):
        return self.encoder.gen_slot_cache(slots, max_seq, dtype)

    def gen_paged_cache(self, pages, page_size, dtype="float32"):
        return self.encoder.gen_paged_cache(pages, page_size, dtype)

    def empty_cache(self, batch):
        """Eager incremental-decode cache (the concat-based ``Cache``
        path ``dynamic_decode`` drives)."""
        from ..core.tensor import to_tensor
        return self.encoder.gen_cache(
            to_tensor(np.zeros((int(batch), 1), np.float32)))

    def forward(self, ids, cache=None, positions=None, attn_mask=None):
        from ..core.tensor import to_tensor
        from ..nn import MultiHeadAttention
        B, L = ids.shape[0], ids.shape[1]
        off = 0
        if cache is not None and isinstance(
                cache[0], MultiHeadAttention.Cache):
            off = cache[0].k.shape[1]
        if positions is None:
            positions = to_tensor(np.broadcast_to(
                np.arange(off, off + L, dtype=np.int64), (B, L)).copy())
        x = self.embed(ids) + self.pos_embed(positions)
        if (cache is not None and len(cache) and
                isinstance(cache[0], MultiHeadAttention.PagedCache)):
            # paged decode: masking derives from the page table +
            # cursor inside paged_attention — never build a mask here
            pass
        elif attn_mask is None and L > 1:
            # causal over the (cached + new) key length: needed for any
            # multi-query pass — the no-cache forward AND the eager
            # concat-cache prefill (single-query decode needs none)
            j = np.arange(off + L)[None, :]
            i = np.arange(L)[:, None]
            attn_mask = to_tensor((j <= off + i)[None, None])
        out = self.encoder(x, attn_mask, cache)
        if cache is None:
            return self.head(out)
        h, new_caches = out
        return self.head(h), new_caches


# ---------------------------------------------------------------------------
# token stream


class TokenStream:
    """Per-request streaming handle: iterate tokens as they decode.

    The engine side ``_put``s tokens and ``_finish``es the stream
    (first-wins, like :class:`~paddle1_tpu.serving.batcher.ServeFuture`);
    the client iterates (``for tok in stream``), collects
    (``result()``), or ``cancel()``s. The buffer of *unconsumed* tokens
    is bounded (``serve_gen_stream_buffer``): when full, the engine
    parks the slot — decode for this request pauses, the device batch
    keeps serving everyone else — until the client drains it.

    ``finish_reason``: ``"eos"`` | ``"length"`` (requested
    ``max_new_tokens`` reached) | ``"deadline"`` | ``"budget"`` (server
    token budget cut the stream short — typed) | ``"cancelled"`` |
    ``"error"`` (incl. a drain that ran out of patience — the typed
    exception says which).
    """

    def __init__(self, buffer_cap: int):
        self._cond = threading.Condition()
        self._cap = int(buffer_cap)
        self._buf: collections.deque = collections.deque()
        self._all: List[int] = []
        self._done = False
        self._exc: Optional[BaseException] = None
        self._cancel_requested = False
        self.finish_reason: Optional[str] = None

    # -- engine side --------------------------------------------------------

    def _writable(self) -> bool:
        return len(self._buf) < self._cap

    def _put(self, tok: int) -> bool:
        with self._cond:
            if self._done:
                return False
            self._buf.append(int(tok))
            self._all.append(int(tok))
            self._cond.notify_all()
        return True

    def _finish(self, reason: str,
                exc: Optional[BaseException] = None) -> bool:
        with self._cond:
            if self._done:
                return False
            self._done = True
            self.finish_reason = reason
            self._exc = exc
            self._cond.notify_all()
        return True

    # -- client side --------------------------------------------------------

    def cancel(self) -> None:
        """Ask the engine to release this request's slot at the next
        step boundary; no further tokens stream. Idempotent; a stream
        that already finished is untouched."""
        with self._cond:
            self._cancel_requested = True
            self._cond.notify_all()

    def done(self) -> bool:
        return self._done

    @property
    def tokens(self) -> List[int]:
        """Every token streamed so far (a snapshot copy)."""
        with self._cond:
            return list(self._all)

    def __iter__(self) -> "TokenStream":
        return self

    def __next__(self) -> int:
        with self._cond:
            while True:
                if self._buf:
                    tok = self._buf.popleft()
                    self._cond.notify_all()  # engine may unpark
                    return tok
                if self._done:
                    # buffered tokens always drain first; a typed
                    # failure surfaces MID-stream, after everything
                    # that was generated before it
                    if self._exc is not None and \
                            self.finish_reason != "cancelled":
                        raise self._exc
                    raise StopIteration
                self._cond.wait()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream finishes; the full token list.
        Raises the stream's typed error (incl. :class:`StreamCancelled`
        after a cancel) — partial tokens stay readable via
        :attr:`tokens`. This IS a consumer: it drains the bounded
        buffer while waiting (``_all`` keeps everything), so a parked
        slot resumes — don't mix it with iteration."""
        with self._cond:
            deadline = (time.monotonic() + timeout
                        if timeout is not None else None)
            while not self._done:
                if self._buf:
                    self._buf.clear()  # consumed; engine may unpark
                    self._cond.notify_all()
                rem = None if deadline is None \
                    else deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    raise DeadlineExceeded(
                        f"TokenStream not finished within {timeout}s — "
                        "the request is still decoding (reader "
                        "deadline only; the stream stays accounted)")
                self._cond.wait(rem)
            if self._exc is not None:
                raise self._exc
            return list(self._all)


class _GenRequest:
    __slots__ = ("prompt", "max_new", "temperature", "top_k", "seed",
                 "stream", "deadline", "t_enq", "truncated_by_budget",
                 "slot", "n_generated", "t_first", "spec",
                 "priority", "resumed", "emitted", "preempted")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 temperature: float, top_k: int, seed: int,
                 deadline_s: Optional[float], stream: TokenStream,
                 truncated_by_budget: bool, priority: int = 0,
                 resumed: int = 0):
        # `prompt` includes any previously-emitted tokens a failover
        # replays (`resumed` = how many of its tail are replayed output,
        # NOT client prompt); `emitted` tracks tokens THIS server
        # produced, so preempt/park re-admission can extend the replay.
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.stream = stream
        self.t_enq = time.monotonic()
        self.deadline = (self.t_enq + deadline_s
                         if deadline_s else None)
        self.truncated_by_budget = truncated_by_budget
        self.slot = -1
        self.priority = int(priority)
        self.resumed = int(resumed)
        self.n_generated = int(resumed)
        self.emitted: List[int] = []
        self.preempted = 0
        self.t_first = 0.0
        self.spec = None  # per-request speculator (engine.spec_tokens>0)


# ---------------------------------------------------------------------------
# engine


class GenerationEngine:
    """Device state + compiled executables of the decode loop.

    Owns the per-layer ``[slots, max_seq, heads, dim]`` KV cache and
    the per-slot cursor/token/RNG/sampling arrays, all donated through
    every dispatch. :meth:`prefill` runs one prompt (padded to its
    length bucket) into a slot and samples the first token;
    :meth:`decode` advances EVERY active slot by one token in one
    dispatch. Slot scheduling (who is active, stream delivery,
    deadlines) lives in :class:`GenerationServer` — the engine is
    purely the device side, so it is reusable under a different front
    end.
    """

    def __init__(self, model, slots: Optional[int] = None,
                 max_seq: Optional[int] = None, prefill_buckets=None,
                 eos_id: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 cache_dtype: str = "float32",
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 pages: Optional[int] = None,
                 prefix_cache: Optional[int] = None,
                 spec_tokens: Optional[int] = None,
                 int8: Optional[bool] = None):
        core_flags.maybe_enable_compilation_cache()
        import jax
        self.metrics = metrics
        self.slots = int(slots if slots is not None
                         else core_flags.flag("serve_gen_slots"))
        self.max_seq = int(max_seq if max_seq is not None
                           else core_flags.flag("serve_gen_max_seq"))
        if self.slots < 1 or self.max_seq < 2:
            raise InvalidArgumentError(
                f"need slots >= 1 and max_seq >= 2, got "
                f"{self.slots}/{self.max_seq}")
        # decode economics (ISSUE 16): paging / speculation / int8 all
        # resolve at construction and ride ONE decode signature
        self.paged = bool(core_flags.flag("serve_gen_paged")
                          if paged is None else paged)
        self.spec_tokens = int(core_flags.flag("serve_gen_spec_tokens")
                               if spec_tokens is None else spec_tokens)
        if self.spec_tokens < 0:
            raise InvalidArgumentError(
                f"spec_tokens must be >= 0, got {self.spec_tokens}")
        # decode window: the fed token + k drafts verified per dispatch.
        # Every window write spans `window` rows, so sequences must stop
        # `decode_margin` short of max_seq — enforced at admission.
        self.window = 1 + self.spec_tokens
        self.decode_margin = self.window - 1
        if self.max_seq <= self.decode_margin + 1:
            raise InvalidArgumentError(
                f"max_seq={self.max_seq} leaves no room under a "
                f"speculative window of {self.window} (margin "
                f"{self.decode_margin}) — shrink serve_gen_spec_tokens")
        self.int8 = bool(core_flags.flag("serve_gen_int8")
                         if int8 is None else int8)
        self.prefill_buckets = self._resolve_prefill_buckets(
            prefill_buckets, self.max_seq)
        self.eos_id = None if eos_id is None else int(eos_id)
        needed_cache = "gen_paged_cache" if self.paged \
            else "gen_slot_cache"
        if not hasattr(model, needed_cache):
            raise InvalidArgumentError(
                "GenerationEngine needs a model with the generation "
                f"contract: {needed_cache}(...) and "
                "forward(ids, cache=, positions=, attn_mask=) -> "
                f"(logits, new_cache); got {type(model).__name__}")
        model_cap = getattr(model, "max_seq", None)
        if model_cap is not None and int(model_cap) < self.max_seq:
            # positions past the model's embedding table would CLAMP
            # under jit (jnp.take semantics) and silently degrade every
            # long sequence — reject the config typed instead
            raise InvalidArgumentError(
                f"engine max_seq={self.max_seq} exceeds the model's "
                f"positional capacity (model.max_seq={int(model_cap)})"
                " — positions past the table would silently clamp; "
                "build the model with max_seq >= the engine's")
        model.eval()
        self._model = model
        self._params = model.functional_state()
        if self.int8:
            # per-channel int8 weight storage; dequant happens INSIDE
            # the trace (_apply_model), so jit args / HBM stay int8
            from ..quantization import quantize_weights_int8
            self._params = quantize_weights_int8(self._params)
        self._lock = locks.make_lock("GenerationEngine._lock")
        # trace-side-effect counters — the "exactly one decode compile"
        # acceptance gate reads decode_compile_count
        self.decode_compile_count = 0
        self.decode_dispatch_count = 0
        self.prefill_compile_counts: Dict[int, int] = {}
        self.prefill_dispatch_counts: Dict[int, int] = {}

        # device state (donated through every dispatch)
        import jax.numpy as jnp
        if self.paged:
            self.page_size = int(
                page_size if page_size is not None
                else core_flags.flag("serve_gen_kv_page_size"))
            if self.page_size < 1:
                raise InvalidArgumentError(
                    f"page_size must be >= 1, got {self.page_size}")
            self.pages_per_slot = -(-self.max_seq // self.page_size)
            n_pages = int(pages if pages is not None
                          else core_flags.flag("serve_gen_kv_pages"))
            if n_pages <= 0:
                # auto: worst case every slot dense, + the parking page
                n_pages = self.slots * self.pages_per_slot + 1
            prefix_entries = int(
                prefix_cache if prefix_cache is not None
                else core_flags.flag("serve_gen_prefix_cache"))
            self.pool = PagePool(n_pages, self.page_size,
                                 prefix_entries)
            cache = model.gen_paged_cache(n_pages, self.page_size,
                                          cache_dtype)
            self._kv = [(c.k.data, c.v.data) for c in cache]
            # host-authoritative page table, mirrored to device on
            # change; rows are parking-filled beyond a slot's chain
            self._table_np = np.full(
                [self.slots, self.pages_per_slot], PARKING_PAGE,
                np.int32)
            self._table = jnp.asarray(self._table_np)
            self._slot_pages: List[List[int]] = [
                [] for _ in range(self.slots)]
            # K+V bytes of ONE page across every layer (sizing + the
            # gen_kv_page_bytes gauge)
            self._page_bytes = sum(
                int(np.prod(k.shape[1:])) * k.dtype.itemsize
                + int(np.prod(v.shape[1:])) * v.dtype.itemsize
                for k, v in self._kv)
        else:
            self.pool = None
            self.page_size = 0
            self.pages_per_slot = 0
            self._page_bytes = 0
            cache = model.gen_slot_cache(self.slots, self.max_seq,
                                         cache_dtype)
            self._kv = [(c.k.data, c.v.data) for c in cache]
            self._table_np = np.zeros([1, 1], np.int32)
            self._table = jnp.asarray(self._table_np)
            self._slot_pages = [[] for _ in range(self.slots)]
        # host mirror of _lengths: page-capacity math and window
        # delivery never pay a device readback for it
        self._host_len = np.zeros([self.slots], np.int64)
        self._warming = False
        self.last_page_faults: Dict[int, KVPoolExhausted] = {}
        self._last_pool_stats: Dict[str, int] = {}
        self._evictions_published = 0
        self._lengths = jnp.zeros([self.slots], jnp.int32)
        self._tokens = jnp.zeros([self.slots], jnp.int32)
        self._keys = jnp.zeros(
            [self.slots] + list(jax.random.key_data(
                jax.random.key(0)).shape), jnp.uint32)
        self._temps = jnp.zeros([self.slots], jnp.float32)
        self._topks = jnp.zeros([self.slots], jnp.int32)

        self._decode_jit = jax.jit(self._decode_fn,
                                   donate_argnums=(1,))
        self._prefill_jits: Dict[int, object] = {}
        # None when debug_jit_sanitizer is off: decode's compile-once
        # contract becomes enforceable (limit=1) and the donated KV
        # cache is poisoned after every dispatch
        self._jsan = jit_sanitizer.site("GenerationEngine")
        # executable cost attribution (obs.costmodel, ISSUE 13):
        # computed lazily per executable on the first instrumented
        # dispatch (obs_metrics on); the HBM census tags the engine's
        # device state per subsystem (weakref — dies with the engine)
        self._decode_cost = None
        self._prefill_costs: Dict[int, object] = {}
        from ..obs import hbm as obs_hbm
        obs_hbm.register("params", self, lambda e: e._params,
                         name="GenerationEngine.params")
        # the page pools/table ride the kv_cache subsystem: census
        # coverage stays 1.0 under paging (ISSUE 16 satellite), and the
        # small per-slot state arrays are accounted rather than leaked
        # into "other"
        obs_hbm.register("kv_cache", self,
                         lambda e: (e._kv, e._table, e._lengths,
                                    e._tokens, e._keys, e._temps,
                                    e._topks),
                         name="GenerationEngine.kv")

    @staticmethod
    def _resolve_prefill_buckets(buckets, max_seq):
        # the batch-bucket policy, retargeted at the prompt-length axis
        # (spec_flag keeps it off the serve_buckets BATCH flag)
        out = resolve_buckets(buckets, max_seq,
                              spec_flag="serve_gen_prefill_buckets")
        if out[-1] > max_seq:
            raise InvalidArgumentError(
                f"prefill bucket {out[-1]} exceeds serve_gen_max_seq="
                f"{max_seq} — a prompt that long could never decode")
        return out

    # -- traced bodies ------------------------------------------------------

    def _apply_model(self, params, ids, caches, positions, attn_mask):
        """Run the model functionally on raw arrays (the
        InferenceEngine idiom: params ride as jit args, dropout off,
        RNG pinned)."""
        import jax
        from ..autograd import engine as autograd_engine
        from ..core.generator import rng_scope
        from ..core.tensor import Tensor
        if self.int8:
            # int8 weights dequantize per-channel inside the trace; XLA
            # fuses the cast+scale into the consuming matmul, so HBM
            # traffic (and the jit args) stay int8
            from ..quantization import dequantize_weights
            params = dequantize_weights(params)
        mask_t = None if attn_mask is None \
            else Tensor(attn_mask, stop_gradient=True)
        with autograd_engine.no_grad(), rng_scope(jax.random.key(0)):
            with self._model.load_functional_state(params):
                logits, new_caches = self._model(
                    Tensor(ids, stop_gradient=True),
                    cache=caches,
                    positions=Tensor(positions, stop_gradient=True),
                    attn_mask=mask_t)
        return logits.data, new_caches

    def _decode_fn(self, params, kv, table, lengths, tokens, keys,
                   temps, topks, active, drafts, ndrafts):
        """Counted wrapper over :meth:`_decode_body` — the increment
        runs only while TRACING (the standard trace-side-effect
        counter). The cost model lowers ``_decode_body`` directly so
        attribution can never corrupt the compile-ONCE accounting."""
        with self._lock:
            self.decode_compile_count += 1
        if self.metrics is not None:
            self.metrics.counter("gen_decode_compiles_total").inc()
        return self._decode_body(params, kv, table, lengths, tokens,
                                 keys, temps, topks, active, drafts,
                                 ndrafts)

    def _decode_body(self, params, kv, table, lengths, tokens, keys,
                     temps, topks, active, drafts, ndrafts):
        """One decode WINDOW for every slot; compiled exactly once.

        The window is ``[fed token, draft_1..draft_k]`` (k =
        ``spec_tokens``; k=0 reduces exactly to the classic one-token
        step). All W rows run through the model in one dispatch;
        row i's logits give the target-distribution sample for position
        pos+i+1, and the draft chain is verified by *equality against
        the engine's own deterministic key schedule*: row i's sample is
        produced iff every earlier draft matched its sample. The RNG
        key advances once per PRODUCED token — so the (seed, token
        index) → draw mapping, and therefore the output stream, is
        bit-identical to non-speculative decode whatever the drafts
        were. Rejected-draft KV rows are stale garbage past the new
        cursor; the next window overwrites them before any mask ever
        exposes them.

        ``active`` gates advancement — inactive slots keep their
        token/length/key, so parking a slot costs nothing and never
        retraces. Paged mode reads/writes through ``table`` (dense mode
        carries a [1,1] placeholder); page faults and draft contents
        are DATA, never shapes, preserving the one-compile contract.
        """
        import jax
        import jax.numpy as jnp
        from ..nn import MultiHeadAttention
        from ..nn.decode import sample_logits_array
        from ..core.tensor import Tensor
        S, M, W = self.slots, self.max_seq, self.window
        pos = jnp.minimum(lengths, M - W)
        ids = jnp.concatenate([tokens[:, None], drafts], axis=1)
        positions = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
        if self.paged:
            caches = [MultiHeadAttention.PagedCache(
                Tensor(k, stop_gradient=True),
                Tensor(v, stop_gradient=True),
                Tensor(table, stop_gradient=True),
                Tensor(pos, stop_gradient=True)) for k, v in kv]
            logits, new_caches = self._apply_model(
                params, ids, caches, positions, None)
        else:
            caches = [MultiHeadAttention.GenCache(
                Tensor(k, stop_gradient=True),
                Tensor(v, stop_gradient=True),
                Tensor(pos, stop_gradient=True)) for k, v in kv]
            # window row i attends keys j <= pos + i (row 0 == the
            # classic "fed token just written AT pos" mask)
            qpos = positions
            mask = (jnp.arange(M)[None, None, None, :]
                    <= qpos[:, None, :, None])
            logits, new_caches = self._apply_model(
                params, ids, caches, positions, mask)
        lg = logits.astype(jnp.float32)              # [S, W, V]
        # dpad[i] = the draft proposed for row i+1 (last column unused)
        dpad = jnp.concatenate(
            [drafts, jnp.zeros((S, 1), drafts.dtype)], axis=1)

        def chain(lg_row, dpad_row, kd0, temp, topk, act, nd):
            def step(carry, x):
                kd, ok = carry
                i, lrow, dnext = x
                kb = jax.random.wrap_key_data(kd)
                s = sample_logits_array(
                    lrow, jax.random.fold_in(kb, 0), temp,
                    topk).astype(jnp.int32)
                kd2 = jnp.where(ok, jax.random.key_data(
                    jax.random.fold_in(kb, 1)), kd)
                ok2 = ok & (i < nd) & (dnext == s)
                return (kd2, ok2), (s, ok)
            (kdf, _), (toks, flags) = jax.lax.scan(
                step, (kd0, act),
                (jnp.arange(W), lg_row, dpad_row))
            return toks, flags, kdf

        toks, flags, new_keys = jax.vmap(chain)(
            lg, dpad, keys, temps, topks, active, ndrafts)
        n_prod = jnp.sum(flags.astype(jnp.int32), axis=1)
        last = jnp.take_along_axis(
            toks, jnp.maximum(n_prod - 1, 0)[:, None], axis=1)[:, 0]
        nxt = jnp.where(n_prod > 0, last, tokens)
        new_lengths = jnp.minimum(lengths + n_prod,
                                  M - self.decode_margin)
        new_kv = [(c.k.data, c.v.data) for c in new_caches]
        return new_kv, new_lengths, nxt, new_keys, toks, flags

    def _prefill_fn_for(self, bucket: int):
        """Build (once per bucket) the counted prefill wrapper over
        :meth:`_prefill_body` (same counted/uncounted split as
        decode)."""
        import jax

        def prefill_fn(params, kv, ids, length, slot, key, temp, topk,
                       row_pages):
            with self._lock:
                self.prefill_compile_counts[bucket] = \
                    self.prefill_compile_counts.get(bucket, 0) + 1
            if self.metrics is not None:
                self.metrics.counter("gen_prefill_compiles_total").inc()
            return self._prefill_body(bucket, params, kv, ids, length,
                                      slot, key, temp, topk, row_pages)
        return jax.jit(prefill_fn, donate_argnums=(1,))

    def _prefill_body(self, bucket, params, kv, ids, length, slot, key,
                      temp, topk, row_pages):
        """The prefill computation: the whole padded prompt in one
        causal pass, K/V written into the slot's cache rows — dense:
        one dynamic_update_slice per layer at the slot row; paged: a
        per-row scatter steered by ``row_pages`` ([bucket] int32, the
        target page per prompt position). Shared prefix pages and
        beyond-prompt padding rows target the parking page, so a reused
        page is NEVER rewritten (bit-stable for every cohabitant) and
        padding garbage never lands in real pages. First token sampled
        from the last REAL position."""
        import jax
        import jax.numpy as jnp
        from ..nn import MultiHeadAttention
        from ..nn.decode import sample_logits_array
        from ..core.tensor import Tensor
        L = bucket
        small = []
        for k_arr, v_arr in kv:
            H, D = k_arr.shape[1 if self.paged else 2], k_arr.shape[3]
            z = jnp.zeros((1, L, H, D), k_arr.dtype)
            small.append(MultiHeadAttention.GenCache(
                Tensor(z, stop_gradient=True),
                Tensor(z, stop_gradient=True),
                Tensor(jnp.zeros((1,), jnp.int32),
                       stop_gradient=True)))
        positions = jnp.arange(L, dtype=jnp.int32)[None]
        causal = jnp.tril(jnp.ones((L, L), bool))[None, None]
        logits, filled = self._apply_model(
            params, ids[None], small, positions, causal)
        new_kv = []
        if self.paged:
            off = jnp.arange(L) % self.page_size
            for (k_arr, v_arr), c in zip(kv, filled):
                new_kv.append((
                    k_arr.at[row_pages, :, off].set(
                        c.k.data[0].astype(k_arr.dtype)),
                    v_arr.at[row_pages, :, off].set(
                        c.v.data[0].astype(v_arr.dtype))))
        else:
            for (k_arr, v_arr), c in zip(kv, filled):
                new_kv.append((
                    jax.lax.dynamic_update_slice(
                        k_arr, c.k.data.astype(k_arr.dtype),
                        (slot, 0, 0, 0)),
                    jax.lax.dynamic_update_slice(
                        v_arr, c.v.data.astype(v_arr.dtype),
                        (slot, 0, 0, 0))))
        last = jnp.take(logits[0], length - 1,
                        axis=0).astype(jnp.float32)
        kb = jax.random.wrap_key_data(key)
        first = sample_logits_array(
            last, jax.random.fold_in(kb, 0), temp, topk)
        carry = jax.random.key_data(jax.random.fold_in(kb, 1))
        return new_kv, first.astype(jnp.int32), carry

    # -- host-side dispatch -------------------------------------------------

    @staticmethod
    def resume_key(seed: int, start_index: int = 0) -> "object":
        """The raw key data that draws token ``start_index + 1`` of the
        request seeded ``seed`` — the replay foundation of mid-stream
        failover. The engine's schedule depends only on (seed, token
        index): prefill starts from ``fold_in(key(seed), 0)`` and every
        PRODUCED token advances the carry once via ``fold_in(k, 1)``,
        so host-advancing the chain ``start_index`` steps and
        prefilling over ``prompt + tokens already emitted`` continues
        the stream bit-identically on any replica (greedy ignores keys
        entirely; sampled draws re-join the exact chain)."""
        import jax
        k = jax.random.fold_in(
            jax.random.key(int(seed) & 0x7FFFFFFF), 0)
        for _ in range(int(start_index)):
            k = jax.random.fold_in(k, 1)
        return jax.random.key_data(k)

    def check_kv_invariants(self, extra_holders=()) -> None:
        """Debug sweep (``FLAGS_debug_kv_refcount``): verify the page
        pool's refcounts against the engine's live slot chains (+ any
        ``extra_holders`` page lists, e.g. chaos-held pages). Raises
        typed :class:`~paddle1_tpu.serving.errors.KVPageAccountingError`
        at the tick that corrupted accounting. No-op when unpaged."""
        if not self.paged:
            return
        holders = [c for c in self._slot_pages if c]
        holders.extend(list(x) for x in extra_holders if x)
        self.pool.check_invariants(holders)

    def bucket_for(self, prompt_len: int) -> int:
        if prompt_len < 1:
            raise InvalidArgumentError(
                f"need a prompt of >= 1 token, got {prompt_len}")
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise InvalidArgumentError(
            f"prompt of {prompt_len} tokens exceeds the largest "
            f"prefill bucket {self.prefill_buckets[-1]} (buckets "
            f"{list(self.prefill_buckets)}) — raise "
            "serve_gen_prefill_buckets/serve_gen_max_seq")

    def _release_slot_pages(self, slot: int) -> None:
        """Drop the slot's page refs and park its table row (paged)."""
        if not self.paged:
            return
        pages = self._slot_pages[slot]
        if pages:
            self.pool.release(pages)
            self._slot_pages[slot] = []
        if (self._table_np[slot] != PARKING_PAGE).any():
            import jax.numpy as jnp
            self._table_np[slot, :] = PARKING_PAGE
            self._table = jnp.asarray(self._table_np)

    def _alloc_prefill_pages(self, slot: int,
                             prompt: np.ndarray) -> np.ndarray:
        """Claim the slot's prefill page chain (prefix-shared head +
        private tail) and return the per-row scatter targets. Shared
        pages' rows target parking — only the FIRST request ever writes
        a shared page, so cohabitants' bits can never be perturbed —
        and the whole chain is refcounted against the slot. Raises
        KVPoolExhausted (after releasing anything claimed) when the
        pool cannot serve; the caller never holds a half-claimed
        chain."""
        P = int(np.shape(prompt)[0])
        ps = self.page_size
        prompt_i32 = np.asarray(prompt, np.int32).reshape(-1)
        self._release_slot_pages(slot)  # warm-up / crash-reuse safety
        shared: List[int] = []
        if not self._warming:
            shared = self.pool.lookup_prefix(prompt_i32)
        n_needed = (P - 1) // ps + 1
        n_shared = min(len(shared), n_needed)
        if n_shared < len(shared):  # over-long hit (can't happen: the
            self.pool.release(shared[n_shared:])  # registry only holds
            shared = shared[:n_shared]            # full-page chains)
        try:
            private = self.pool.alloc(n_needed - n_shared)
        except KVPoolExhausted:
            self.pool.release(shared)
            raise
        chain = shared + private
        if not self._warming:
            self.pool.register_prefix(prompt_i32, chain)
        import jax.numpy as jnp
        self._slot_pages[slot] = chain
        self._table_np[slot, :] = PARKING_PAGE
        self._table_np[slot, :len(chain)] = chain
        self._table = jnp.asarray(self._table_np)
        if self.metrics is not None:
            from ..obs.registry import metrics_on
            if metrics_on():
                self.metrics.counter(
                    "gen_kv_prefix_hits_total").inc(n_shared)
        # per-row targets: shared head + padding rows → parking
        row_pages = np.full([self.bucket_for(P)], PARKING_PAGE,
                            np.int32)
        for i in range(n_shared * ps, P):
            row_pages[i] = chain[i // ps]
        return row_pages

    def prefill(self, slot: int, prompt: np.ndarray, temperature: float,
                top_k: int, seed: int, start_index: int = 0) -> int:
        """Run one prompt into ``slot``; returns the first generated
        token (host int). One dispatch on the bucket executable.

        ``start_index > 0`` is the failover/preemption replay path:
        ``prompt`` then carries the client prompt PLUS the first
        ``start_index`` tokens already emitted elsewhere, and the RNG
        key resumes at :meth:`resume_key` — the returned "first" token
        is token ``start_index + 1`` of the original stream, bit-
        identical to an uninterrupted run (the prefill logits at the
        last real position equal the decode step's, and the draw key is
        the same chain entry)."""
        import jax
        import jax.numpy as jnp
        P = int(np.shape(prompt)[0])
        bucket = self.bucket_for(P)
        if P + 1 > self.max_seq - self.decode_margin:
            raise InvalidArgumentError(
                f"prompt of {P} tokens leaves no room to generate "
                f"within serve_gen_max_seq={self.max_seq} (speculative "
                f"window margin {self.decode_margin})")
        if self.paged:
            row_pages = self._alloc_prefill_pages(slot, prompt)
        else:
            row_pages = np.zeros([bucket], np.int32)
        fn = self._prefill_jits.get(bucket)
        if fn is None:
            fn = self._prefill_jits.setdefault(
                bucket, self._prefill_fn_for(bucket))
        ids = np.zeros([bucket], np.int32)
        ids[:P] = np.asarray(prompt, np.int32)
        base = self.resume_key(seed, start_index)
        with self._lock:
            self.prefill_dispatch_counts[bucket] = \
                self.prefill_dispatch_counts.get(bucket, 0) + 1
        donated = None
        if self._jsan is not None:
            donated = [a for pair in self._kv for a in pair]
            self._jsan.guard_args(donated, "prefill")
        self._kv, first, carry = fn(
            self._params, self._kv, jnp.asarray(ids),
            np.int32(P), np.int32(slot), base,
            np.float32(temperature), np.int32(top_k),
            jnp.asarray(row_pages))
        if donated is not None:
            self._jsan.poison_donated(donated)
        if self.metrics is not None \
                and bucket not in self._prefill_costs:
            self._maybe_publish_prefill_cost(bucket)
        first = int(np.asarray(first))
        # slot bookkeeping (small host-side .at updates, off the jitted
        # path so they can't force a retrace)
        self._lengths = self._lengths.at[slot].set(np.int32(P))
        self._host_len[slot] = P
        self._tokens = self._tokens.at[slot].set(np.int32(first))
        self._keys = self._keys.at[slot].set(carry)
        self._temps = self._temps.at[slot].set(np.float32(temperature))
        self._topks = self._topks.at[slot].set(np.int32(top_k))
        return first

    def ensure_page_capacity(self, active_mask: np.ndarray
                             ) -> Dict[int, BaseException]:
        """Page-fault handler, run on the host BEFORE each decode
        dispatch (paged mode): any active slot whose next ``window``
        writes would spill past its mapped chain gets fresh pages
        appended to its table row. Faults change only the table *data*
        — shapes are pinned at ``[slots, max_pages_per_slot]`` — so the
        decode executable is untouched (compile-once survives growth).
        Returns ``{slot: KVPoolExhausted}`` for slots the pool could
        not extend; the caller masks those out and finishes them."""
        if not self.paged:
            return {}
        import jax.numpy as jnp
        failed: Dict[int, BaseException] = {}
        faulted = 0
        dirty = False
        for s in range(self.slots):
            if not bool(active_mask[s]):
                continue
            need = min(
                (int(self._host_len[s]) + self.window - 1)
                // self.page_size + 1,
                self.pages_per_slot)
            have = len(self._slot_pages[s])
            if need <= have:
                continue
            try:
                fresh = self.pool.alloc(need - have)
            except KVPoolExhausted as e:
                failed[s] = e
                continue
            self._table_np[s, have:have + len(fresh)] = fresh
            self._slot_pages[s].extend(fresh)
            faulted += len(fresh)
            dirty = True
        if dirty:
            self._table = jnp.asarray(self._table_np)
        if faulted and self.metrics is not None:
            from ..obs.registry import metrics_on
            if metrics_on():
                self.metrics.counter(
                    "gen_kv_page_faults_total").inc(faulted)
        return failed

    def decode(self, active_mask: np.ndarray,
               drafts: Optional[np.ndarray] = None,
               ndrafts: Optional[np.ndarray] = None):  # hot-path: one dispatch per step
        """One decode step for the whole slot batch; returns
        ``(tokens, accepted)`` — both ``[slots, window]`` host arrays.
        ``tokens[s, i]`` is the i-th token the sample chain produced;
        ``accepted[s, i]`` marks the chain entries that are real output
        (always column 0 for live slots; further columns only when
        speculation accepted draft tokens). Exactly one device
        dispatch regardless of drafts, faults, or arrival pattern."""
        import jax.numpy as jnp
        self.last_page_faults = self.ensure_page_capacity(active_mask)
        if self.last_page_faults:
            active_mask = np.asarray(active_mask, bool).copy()
            for s in self.last_page_faults:
                active_mask[s] = False
        if drafts is None:
            drafts = np.zeros([self.slots, self.spec_tokens], np.int32)
        if ndrafts is None:
            ndrafts = np.zeros([self.slots], np.int32)
        with self._lock:
            self.decode_dispatch_count += 1
        donated = None
        if self._jsan is not None:
            donated = [a for pair in self._kv for a in pair]
            self._jsan.guard_args(donated, "decode")
        (self._kv, self._lengths, self._tokens, self._keys, toks,
         flags) = self._decode_jit(
            self._params, self._kv, self._table, self._lengths,
            self._tokens, self._keys, self._temps, self._topks,
            jnp.asarray(active_mask, bool),
            jnp.asarray(drafts, jnp.int32).reshape(
                self.slots, self.spec_tokens) if self.spec_tokens
            else jnp.zeros([self.slots, 0], jnp.int32),
            jnp.asarray(ndrafts, jnp.int32).reshape(self.slots))
        if donated is not None:
            self._jsan.poison_donated(donated)
            # the compile-once contract, enforceable: a second decode
            # compile means a signature leaked into the pinned shape
            self._jsan.note_signatures(self.decode_compile_count,
                                       kind="decode recompile", limit=1)
        if self.metrics is not None and self._decode_cost is None:
            self._maybe_publish_decode_cost()
        jit_sanitizer.note_host_sync("gen_token_readback")
        toks_np = np.asarray(toks)  # noqa: hidden-host-sync — the ONE intended readback
        flags_np = np.asarray(flags, bool)
        self._host_len += flags_np.sum(axis=1).astype(np.int64)
        np.minimum(self._host_len,
                   self.max_seq - self.decode_margin,
                   out=self._host_len)
        return toks_np, flags_np

    # -- executable cost attribution (ISSUE 13) -----------------------------

    def decode_cost(self):
        """FLOPs + bytes of ONE decode dispatch (the whole slot batch,
        one token each) — XLA cost analysis of an UNCOUNTED lowering
        of :meth:`_decode_body` (lowering the counted jit would break
        the compile-ONCE accounting). Memoized: the decode signature
        is pinned, so one analysis covers the engine's lifetime."""
        if self._decode_cost is None:
            import jax
            import jax.numpy as jnp
            from ..obs import costmodel as obs_costmodel
            args = (self._params, self._kv, self._table, self._lengths,
                    self._tokens, self._keys, self._temps, self._topks,
                    jnp.zeros([self.slots], bool),
                    jnp.zeros([self.slots, self.spec_tokens],
                              jnp.int32),
                    jnp.zeros([self.slots], jnp.int32))
            fb = obs_costmodel.tree_size_cost(
                self._params, batch=self._tokens, extra=self._kv)
            self._decode_cost = obs_costmodel.analyze(
                lambda: jax.jit(self._decode_body).lower(*args),
                fallback=fb)
        return self._decode_cost

    def _maybe_publish_decode_cost(self) -> None:
        from ..obs.registry import metrics_on
        if not metrics_on():
            return
        cost = self.decode_cost()
        self.metrics.gauge("gen_decode_flops").set(cost.flops)
        self.metrics.gauge("gen_decode_bytes").set(cost.bytes_accessed)
        self.metrics.gauge("gen_cost_exact").set(
            1.0 if cost.exact else 0.0)

    def prefill_cost(self, bucket: int):
        """FLOPs + bytes of one prefill dispatch at ``bucket`` —
        same uncounted-lowering discipline as :meth:`decode_cost`."""
        c = self._prefill_costs.get(bucket)
        if c is None:
            import jax
            import jax.numpy as jnp
            import numpy as _np
            from ..obs import costmodel as obs_costmodel
            ids = jnp.zeros([bucket], jnp.int32)
            base = jax.random.key_data(jax.random.fold_in(
                jax.random.key(0), 0))
            fb = obs_costmodel.tree_size_cost(self._params, batch=ids,
                                              extra=self._kv)
            c = obs_costmodel.analyze(
                lambda: jax.jit(
                    lambda *a: self._prefill_body(bucket, *a)).lower(
                    self._params, self._kv, ids, _np.int32(1),
                    _np.int32(0), base, _np.float32(0.0),
                    _np.int32(0), jnp.zeros([bucket], jnp.int32)),
                fallback=fb)
            self._prefill_costs[bucket] = c
        return c

    def _maybe_publish_prefill_cost(self, bucket: int) -> None:
        from ..obs.registry import metrics_on
        if not metrics_on():
            return
        cost = self.prefill_cost(bucket)
        self.metrics.gauge(f"gen_prefill_bucket_{bucket}_flops").set(
            cost.flops)
        self.metrics.gauge(f"gen_prefill_bucket_{bucket}_bytes").set(
            cost.bytes_accessed)

    def publish_kv_metrics(self) -> None:
        """Mirror the page pool's host accounting as gauges/counters
        (paged mode; no-op otherwise). ``gen_kv_page_evictions_total``
        publishes the pool's cumulative count via ``inc(delta)`` so the
        counter stays monotone across calls."""
        if not self.paged or self.metrics is None:
            return
        st = self.pool.stats()
        self._last_pool_stats = st
        self.metrics.gauge("gen_kv_pages_in_use").set(
            st["pages_in_use"])
        self.metrics.gauge("gen_kv_pages_free").set(st["pages_free"])
        self.metrics.gauge("gen_kv_pages_cached").set(
            st["pages_cached"])
        self.metrics.gauge("gen_kv_page_bytes").set(self._page_bytes)
        ev = self.metrics.counter("gen_kv_page_evictions_total")
        ev.inc(st["evictions"] - self._evictions_published)
        self._evictions_published = st["evictions"]

    def release(self, slot: int) -> None:
        """Free a slot: reset its cursor so idle writes stay parked at
        row 0 (the next prefill overwrites everything it will read) and
        — in paged mode — return its page refs to the pool in the SAME
        call (the cancel/deadline contract: by the time the scheduler
        tick that retired the request ends, its pages are reusable)."""
        self._lengths = self._lengths.at[slot].set(np.int32(0))
        self._host_len[slot] = 0
        self._release_slot_pages(slot)

    def warm_up(self) -> int:
        """Pre-compile every prefill bucket plus the decode executable
        (first-token latency stops including XLA compiles). Returns the
        number of executables compiled. Slot state is reset after.
        Warm-up prompts bypass the prefix registry (``_warming``): the
        zero-token probe prompts must not squat pages or pollute the
        prefix cache."""
        import jax
        import jax.numpy as jnp
        self._warming = True
        try:
            n = 0
            for b in self.prefill_buckets:
                self.prefill(0, np.zeros(
                    [min(b, self.max_seq - self.window)],
                    np.int32), 0.0, 0, 0)
                n += 1
            self.decode(np.zeros([self.slots], bool))
            n += 1
            jax.block_until_ready(self._kv[0][0])
        finally:
            self._warming = False
        self.release(0)
        self._lengths = jnp.zeros([self.slots], jnp.int32)
        self._tokens = jnp.zeros([self.slots], jnp.int32)
        self._host_len[:] = 0
        return n


# ---------------------------------------------------------------------------
# server


class GenerationServer:
    """Streaming front end over a :class:`GenerationEngine`: admission
    control, per-request deadlines/token budgets, graceful drain — the
    PR 4 Server contracts with token-level accounting. One loop thread
    owns all slot scheduling (iteration-level continuous batching: it
    admits new prompts into free slots between decode steps)."""

    def __init__(self, model, slots: Optional[int] = None,
                 max_seq: Optional[int] = None, prefill_buckets=None,
                 eos_id: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 stream_buffer: Optional[int] = None,
                 warmup: bool = False,
                 metrics: Optional[ServingMetrics] = None,
                 preempt: Optional[bool] = None):
        self.metrics = metrics if metrics is not None else ServingMetrics()
        if isinstance(model, GenerationEngine):
            if (slots is not None or max_seq is not None
                    or prefill_buckets is not None):
                raise InvalidArgumentError(
                    "slots/max_seq/prefill_buckets cannot be applied "
                    "to a pre-built GenerationEngine — pass them to "
                    "GenerationEngine(), or hand the raw model over")
            self.engine = model
            self.engine.metrics = self.metrics  # latest-wins rebind
            if eos_id is not None:
                self.engine.eos_id = int(eos_id)
        else:
            self.engine = GenerationEngine(
                model, slots=slots, max_seq=max_seq,
                prefill_buckets=prefill_buckets, eos_id=eos_id,
                metrics=self.metrics)
        self.token_budget = int(
            token_budget if token_budget is not None
            else core_flags.flag("serve_gen_token_budget"))
        dl = deadline_ms if deadline_ms is not None \
            else core_flags.flag("serve_deadline_ms")
        self.default_deadline_ms = float(dl) if dl else None
        self.queue_depth = int(
            queue_depth if queue_depth is not None
            else core_flags.flag("serve_queue_depth"))
        self.stream_buffer = int(
            stream_buffer if stream_buffer is not None
            else core_flags.flag("serve_gen_stream_buffer"))
        # KV-pressure graceful degradation (preempt/park/re-admit
        # instead of KVPoolExhausted) — only meaningful under paging
        self.preempt = bool(core_flags.flag("serve_gen_preempt")
                            if preempt is None else preempt) \
            and self.engine.paged
        self._warmup = bool(warmup)
        self._q: "queue.Queue[_GenRequest]" = queue.Queue(self.queue_depth)
        self._drain_event = threading.Event()
        self._admit_lock = locks.make_lock("GenerationServer._admit_lock")
        self._accepting = False          # guarded-by: self._admit_lock
        self._loop: Optional[_GenerationLoop] = None
        self._seed_counter = [0]         # guarded-by: self._admit_lock

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "GenerationServer":
        if self._loop is not None and self._loop.is_alive():
            return self
        self._drain_event.clear()
        supervised = core_health.supervised()
        core_health.beat()
        core_health.add_drain_callback(self._drain_event.set)
        if core_health.drain_requested():
            self._drain_event.set()
        if not supervised and threading.current_thread() is \
                threading.main_thread():
            from .server import install_standalone_sigterm_drain
            install_standalone_sigterm_drain()
        if self._warmup:
            n = self.engine.warm_up()
            self.metrics.counter("warmup_executables_total").inc(n)
        self._loop = _GenerationLoop(self.engine, self._q,
                                     self.metrics, self._drain_event,
                                     preempt=self.preempt)
        self._loop.start()
        with self._admit_lock:
            self._accepting = True
        return self

    @property
    def running(self) -> bool:
        return (self._loop is not None and self._loop.is_alive()
                and self._accepting)

    def __enter__(self) -> "GenerationServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.drain()
        return False

    # -- request path -------------------------------------------------------

    def submit(self, prompt_ids: Sequence[int],
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               seed: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               priority: int = 0,
               resume_tokens: Optional[Sequence[int]] = None
               ) -> TokenStream:
        """Enqueue one prompt; returns its :class:`TokenStream`.
        Sheds with :class:`ServerOverloaded` (bounded queue) or raises
        :class:`ServerClosed` (draining/stopped) synchronously.
        ``temperature<=0`` is greedy; ``seed`` pins the sampled draws
        (per-request stream — a request's tokens are identical whether
        it decodes alone or in a full batch).

        ``priority`` (0 = highest) steers KV-pressure preemption under
        ``serve_gen_preempt``: lower-priority streams yield pages
        first. ``resume_tokens`` is the mid-stream failover replay
        path: the tokens a previous replica already emitted for this
        (prompt, seed) stream — they are prefilled (not re-delivered),
        the RNG chain is advanced past them, and the stream continues
        from token ``len(resume_tokens) + 1``, bit-identical to the
        uninterrupted run. ``max_new_tokens`` counts the ORIGINAL
        target (resumed tokens included), so budgets and length caps
        land on the same token they always would."""
        if not self._accepting or self._drain_event.is_set():
            raise ServerClosed(
                "generation server is draining/stopped — not admitting")
        if self._loop is None or not self._loop.is_alive():
            raise ServerClosed(
                "generation server not started (or its loop died: "
                f"{self._loop.fatal!r})" if self._loop is not None
                else "generation server not started — call start()")
        prompt = np.asarray(
            getattr(prompt_ids, "numpy", lambda: prompt_ids)(),
            ).astype(np.int64).reshape(-1)
        if prompt.size < 1:
            raise InvalidArgumentError("submit needs >= 1 prompt token")
        resume = np.asarray(
            [] if resume_tokens is None else resume_tokens,
            np.int64).reshape(-1)
        full = np.concatenate([prompt, resume]) if resume.size \
            else prompt
        self.engine.bucket_for(full.size)  # typed on oversize NOW
        # room is counted from the ORIGINAL prompt: the resumed stream
        # must cap at the same total token the uninterrupted run would
        room = (self.engine.max_seq - int(prompt.size)
                - self.engine.decode_margin)
        if room < 1 or room <= resume.size:
            raise InvalidArgumentError(
                f"prompt of {prompt.size} (+{resume.size} resumed) "
                f"tokens leaves no room to generate within "
                f"max_seq={self.engine.max_seq} (speculative window "
                f"margin {self.engine.decode_margin})")
        asked = int(max_new_tokens) if max_new_tokens is not None \
            else self.token_budget
        if asked < 1:
            raise InvalidArgumentError(
                f"max_new_tokens must be >= 1, got {asked}")
        # the server-side budget/capacity cap: a stream cut short by it
        # fails typed mid-stream (DeadlineExceeded) instead of silently
        # truncating — the client asked for more than it will get
        max_new = min(asked, self.token_budget, room)
        truncated = max_new < asked
        if resume.size >= max_new:
            raise InvalidArgumentError(
                f"resume_tokens already carries {resume.size} of a "
                f"{max_new}-token stream — nothing left to generate "
                "(the stream had finished; don't re-admit it)")
        if resume.size and seed is None:
            raise InvalidArgumentError(
                "resume_tokens needs the original seed — a replayed "
                "continuation is only bit-identical on the same "
                "(seed, token index) chain")
        if seed is None:
            with self._admit_lock:
                self._seed_counter[0] += 1
                seed = self._seed_counter[0]
        dl = deadline_ms if deadline_ms is not None \
            else self.default_deadline_ms
        stream = TokenStream(self.stream_buffer)
        req = _GenRequest(full.astype(np.int32), max_new,
                          float(temperature), int(top_k), int(seed),
                          dl / 1e3 if dl else None, stream, truncated,
                          priority=int(priority),
                          resumed=int(resume.size))
        with self._admit_lock:
            if not self._accepting or self._drain_event.is_set():
                raise ServerClosed(
                    "generation server is draining/stopped — not "
                    "admitting")
            self.metrics.counter("requests_total").inc()
            try:
                self._q.put_nowait(req)
            except queue.Full:
                self.metrics.counter("shed_total").inc()
                raise ServerOverloaded(
                    f"generation queue depth {self.queue_depth} "
                    "exhausted — request shed (scale out, raise "
                    "serve_queue_depth, or slow the client)") from None
        lo = self._loop
        if self._drain_event.is_set() and lo is not None \
                and lo.drained.is_set():
            # lost the admission race against a lockless drain latch
            # (SIGTERM/health callback): nothing will read the queue —
            # resolve typed instead of hanging the stream
            lo._fail_queued(ServerClosed(
                "generation server drained while the request was "
                "being admitted"))
        return stream

    def generate(self, prompt_ids, **kw) -> List[int]:
        """Synchronous convenience: ``submit(...).result()``."""
        return self.submit(prompt_ids, **kw).result()

    # -- drain --------------------------------------------------------------

    def wait(self, poll_s: float = 0.1,
             timeout: Optional[float] = None) -> dict:
        t0 = time.monotonic()
        while not self._drain_event.is_set():
            if timeout is not None and time.monotonic() - t0 >= timeout:
                break
            core_health.beat()
            time.sleep(poll_s)
        return self.drain()

    def drain(self, timeout: float = 30.0) -> dict:
        """Graceful shutdown: stop admitting, flush every accepted
        stream — finish decoding what's owed within ``timeout``, fail
        the rest typed — and report. ``unaccounted`` (requests) and
        ``tokens_owed`` are both ≡ 0 by construction; the report proves
        it."""
        with self._admit_lock:
            self._accepting = False
            self._drain_event.set()
        drained = True
        if self._loop is not None:
            drained = self._loop.drained.wait(timeout)
            if not drained:
                self._loop.abort(DeadlineExceeded(
                    f"generation drain timed out after {timeout}s"))
                self._loop.drained.wait(max(timeout, 1.0))
            self._loop.join(timeout=max(timeout, 1.0))
            self._loop._fail_queued(ServerClosed(
                "generation server drained while the request was "
                "being admitted"))
        core_health.remove_drain_callback(self._drain_event.set)
        snap = self.metrics.snapshot()
        c = snap["counters"]
        report = {
            "drained": bool(drained),
            "fatal": (repr(self._loop.fatal) if self._loop is not None
                      and self._loop.fatal is not None else None),
            "accepted": (c.get("requests_total", 0)
                         - c.get("shed_total", 0)),
            "completed": c.get("streams_completed_total", 0),
            "deadline_failed": c.get("deadline_expired_total", 0),
            "cancelled": c.get("streams_cancelled_total", 0),
            "errors": c.get("errors_total", 0),
            "shed": c.get("shed_total", 0),
            "tokens_generated": c.get("tokens_generated_total", 0),
            "tokens_streamed": c.get("tokens_streamed_total", 0),
            "tokens_dropped": c.get("tokens_dropped_total", 0),
            "decode_compiles": self.engine.decode_compile_count,
            "decode_dispatches": self.engine.decode_dispatch_count,
            "prefill_compile_counts": dict(
                self.engine.prefill_compile_counts),
        }
        report["unaccounted"] = (
            report["accepted"] - report["completed"]
            - report["deadline_failed"] - report["cancelled"]
            - report["errors"])
        report["tokens_owed"] = (
            report["tokens_generated"] - report["tokens_streamed"]
            - report["tokens_dropped"])
        if self.engine.paged:
            # pages held by anything but the (intentionally warm)
            # prefix cache after drain = a leak; ≡ 0 by construction
            st = self.engine.pool.stats()
            report["kv_pages_owed"] = (
                st["pages_in_use"] - st["pages_cached"])
        return report

    stop = drain


class _GenerationLoop(threading.Thread):
    """The scheduler thread: admits prompts into free slots, runs one
    decode dispatch per iteration for every active slot, delivers
    tokens, enforces deadlines/budgets, and answers chaos."""

    _POLL_S = 0.02

    def __init__(self, engine: GenerationEngine,
                 q: "queue.Queue", metrics: ServingMetrics,
                 drain_event: threading.Event,
                 preempt: bool = False):
        super().__init__(name="p1t-generation-loop", daemon=True)
        self.engine = engine
        self.q = q
        self.metrics = metrics
        self.drain = drain_event
        self.drained = threading.Event()
        self.fatal: Optional[BaseException] = None
        self._abort_exc: Optional[BaseException] = None
        self._by_slot: Dict[int, _GenRequest] = {}
        self._free: List[int] = list(range(engine.slots))
        self._spec_proposed = 0
        self._spec_accepted = 0
        # KV-pressure graceful degradation (serve_gen_preempt)
        self._preempt = bool(preempt) and engine.paged
        self._ceiling = float(
            core_flags.flag("serve_gen_pressure_ceiling"))
        # admission-deferred (pressure-gated) requests, FIFO-preserving
        self._pending: collections.deque = collections.deque()
        # preempted/parked live streams awaiting replay re-admission
        self._parked: List[_GenRequest] = []
        # gen_page_pressure chaos: pages the scheduler itself holds
        self._chaos_pages: List[int] = []
        self._chaos_release_tick = 0
        self._tick = 0
        self._debug_refcount = bool(
            core_flags.flag("debug_kv_refcount"))

    def abort(self, exc: BaseException) -> None:
        """A drain that ran out of patience: fail everything still in
        flight typed at the next loop boundary."""
        self._abort_exc = exc

    # -- resolution helpers (single-threaded: only this thread calls) -------

    def _deliver(self, req: _GenRequest, tok: int) -> None:
        m = self.metrics
        m.counter("tokens_generated_total").inc()
        if req.stream._put(tok):
            m.counter("tokens_streamed_total").inc()
        else:
            m.counter("tokens_dropped_total").inc()
        req.n_generated += 1
        req.emitted.append(int(tok))

    def _finish(self, req: _GenRequest, reason: str,
                exc: Optional[BaseException] = None) -> None:
        if req.stream._finish(reason, exc):
            m = self.metrics
            if reason in ("eos", "length"):
                m.counter("streams_completed_total").inc()
                m.record_response()
            elif reason == "cancelled":
                m.counter("streams_cancelled_total").inc()
            elif reason in ("deadline", "budget"):
                m.counter("deadline_expired_total").inc()
            else:
                m.counter("errors_total").inc()
            fresh = req.n_generated - req.resumed
            if fresh > 0 and req.t_first:
                dt = max(time.monotonic() - req.t_first, 1e-9)
                m.histogram("tokens_per_s").observe(fresh / dt)
        if req.slot >= 0:
            self.engine.release(req.slot)
            import bisect
            bisect.insort(self._free, req.slot)
            del self._by_slot[req.slot]
            req.slot = -1

    def _fail_queued(self, exc: BaseException) -> None:
        while True:
            try:
                req = self.q.get_nowait()
            except queue.Empty:
                return
            if req.stream._finish("error", exc):
                self.metrics.counter("errors_total").inc()

    def _fail_inflight(self, exc: BaseException, reason="error") -> None:
        for slot in list(self._by_slot):
            self._finish(self._by_slot[slot], reason, exc)
        # parked (preempted) and pressure-deferred requests are owed a
        # typed answer too — they were accepted
        for req in self._parked:
            self._finish(req, reason, exc)
        self._parked = []
        while self._pending:
            self._finish(self._pending.popleft(), reason, exc)

    # -- scheduling ---------------------------------------------------------

    def _next_request(self) -> Optional[_GenRequest]:
        """Pressure-deferred requests re-try before fresh arrivals
        (FIFO is preserved: a deferral pushes back to the deque head)."""
        if self._pending:
            return self._pending.popleft()
        try:
            return self.q.get_nowait()
        except queue.Empty:
            return None

    def _admissible(self, req: _GenRequest) -> Optional[bool]:
        """Pressure gate (``serve_gen_preempt``): True = admit now,
        False = defer (the pool is too full — never a failure), None =
        this request could never fit the whole pool even alone (the
        ONLY admission shape that still fails typed)."""
        eng = self.engine
        if not self._preempt or not eng.paged:
            return True
        ps = eng.page_size
        orig_p = len(req.prompt) - req.resumed
        worst = min(-(-(orig_p + req.max_new) // ps),
                    eng.pages_per_slot)
        total = eng.pool.num_pages - 1
        if worst > total:
            return None
        pf = len(req.prompt) + len(req.emitted)
        need = (pf - 1) // ps + 1
        st = eng.pool.stats()
        if need > st["pages_free"] + st["pages_cached"]:
            return False  # not even eviction could serve the prefill
        live = st["pages_in_use"] - st["pages_cached"]
        if live > 0 and live + need > self._ceiling * total:
            return False  # defer: keep decode-growth headroom
        return True

    def _park(self, req: _GenRequest, why: str) -> None:
        """Preempt a live stream: release its pages THIS tick, park the
        request, re-admit later via the bit-identical replay path."""
        slot = req.slot
        self.engine.release(slot)
        import bisect
        bisect.insort(self._free, slot)
        del self._by_slot[slot]
        req.slot = -1
        req.spec = None
        req.preempted += 1
        self._parked.append(req)
        m = self.metrics
        m.counter("gen_preemptions_total").inc()
        m.gauge("gen_parked_streams").set(len(self._parked))
        obs_events.emit("gen_stream_preempt", slot=slot,
                        tokens=req.n_generated, priority=req.priority,
                        why=why)

    def _handle_fault(self, slot: int, exc: BaseException) -> None:
        """A decode page fault the pool could not serve. Preempt off:
        fail that stream typed (the PR 16 contract). Preempt on: shed
        pressure instead — the pool already LRU-evicted every cached
        prefix; now preempt strictly-lower-priority victims (longest
        deadline slack first) until the fault fits, else park the
        faulting stream itself. Nothing client-visible either way."""
        req = self._by_slot.get(slot)
        if req is None:
            return
        if not self._preempt:
            self._finish(req, "error", exc)
            return
        eng = self.engine
        need = max(
            (int(eng._host_len[slot]) + eng.window - 1)
            // eng.page_size + 1 - len(eng._slot_pages[slot]), 1)
        now = time.monotonic()

        def slack(r: _GenRequest) -> float:
            return float("inf") if r.deadline is None \
                else r.deadline - now
        victims = sorted(
            (r for s, r in self._by_slot.items()
             if s != slot and r.priority > req.priority),
            key=lambda r: (r.priority, slack(r)), reverse=True)
        while victims and eng.pool.free_pages < need:
            self._park(victims.pop(0),
                       "preempted by higher-priority page fault")
        if eng.pool.free_pages < need:
            # no (more) eligible victims: the faulting stream yields
            self._park(req, "parked under KV pressure")

    def _readmit_parked(self, now: float) -> None:
        """Re-admit parked streams (before fresh arrivals — they are
        older) from ``prompt + everything already emitted`` with the
        key chain advanced past it: the continuation is bit-identical
        to never having been preempted. Cancels/deadlines apply while
        parked too."""
        if not self._parked:
            return
        # snapshot: _admit_one can park a request straight back (pool
        # miss at prefill) — it lands on the emptied self._parked and
        # is merged below, never mutated under iteration
        work = self._parked
        self._parked = []
        keep: List[_GenRequest] = []
        for req in work:
            if req.stream._cancel_requested:
                self._finish(req, "cancelled", StreamCancelled(
                    f"cancelled after {req.n_generated} tokens "
                    "(while parked)"))
                continue
            if req.deadline is not None and now > req.deadline:
                self._finish(req, "deadline", DeadlineExceeded(
                    f"wall deadline exceeded after {req.n_generated} "
                    "tokens (while parked under KV pressure)"))
                continue
            if not self._free:
                keep.append(req)
                continue
            ok = self._admissible(req)
            if ok is None:
                self._finish(req, "error", KVPoolExhausted(
                    "parked stream can never fit the page pool alone "
                    "— raise serve_gen_kv_pages"))
                continue
            if not ok:
                keep.append(req)
                continue
            if self._admit_one(req, now):
                self.metrics.counter(
                    "gen_preempt_readmits_total").inc()
        self._parked = keep + self._parked
        self.metrics.gauge("gen_parked_streams").set(
            len(self._parked))

    def _admit_one(self, req: _GenRequest, now: float) -> bool:
        """Claim the lowest free slot and prefill (fresh admission and
        parked/resumed replay share this path)."""
        slot = self._free.pop(0)
        req.slot = slot
        self._by_slot[slot] = req
        prior = req.resumed + len(req.emitted)
        pp = req.prompt if not req.emitted else np.concatenate(
            [req.prompt, np.asarray(req.emitted, np.int32)])
        try:
            t0 = time.monotonic()
            first = self.engine.prefill(
                slot, pp, req.temperature, req.top_k, req.seed,
                start_index=prior)
            self.metrics.histogram("prefill_ms").observe(
                (time.monotonic() - t0) * 1e3)
            if not req.t_first:
                self.metrics.histogram("queue_ms").observe(
                    (t0 - req.t_enq) * 1e3)
        except KVPoolExhausted as e:
            # raced the admission estimate: under preemption park it
            # (never a client-visible failure); otherwise typed
            import bisect
            bisect.insort(self._free, slot)
            del self._by_slot[slot]
            req.slot = -1
            if self._preempt:
                req.preempted += 1
                self._parked.append(req)
                self.metrics.counter("gen_preemptions_total").inc()
                return False
            self._finish(req, "error", e)
            return False
        except Exception as e:
            self._finish(req, "error", e)
            return False
        if not req.t_first:
            req.t_first = time.monotonic()
        if self.engine.spec_tokens > 0:
            req.spec = NGramSpeculator(
                pp, self.engine.spec_tokens,
                n=int(core_flags.flag("serve_gen_spec_ngram")))
            req.spec.observe(first)
        self._deliver(req, first)
        self._maybe_complete(req, first)
        return True

    def _admit(self) -> None:
        """Claim free slots for queued prompts (iteration-level
        scheduling: runs between decode steps, so a late request joins
        the RUNNING batch). A drain keeps admitting — queued requests
        were accepted and are owed an answer — while `submit` has
        already stopped new arrivals. Under ``serve_gen_preempt``,
        parked streams re-admit first and fresh admissions are
        pressure-gated (deferred, never failed)."""
        now = time.monotonic()
        self._readmit_parked(now)
        while self._free:
            req = self._next_request()
            if req is None:
                return
            now = time.monotonic()
            if req.stream._cancel_requested:
                self._finish(req, "cancelled", StreamCancelled(
                    "cancelled before decoding started"))
                continue
            if req.deadline is not None and now > req.deadline:
                self._finish(req, "deadline", DeadlineExceeded(
                    f"request expired after "
                    f"{(now - req.t_enq) * 1e3:.1f}ms in queue — "
                    "never prefetched into a slot"))
                continue
            ok = self._admissible(req)
            if ok is None:
                self._finish(req, "error", KVPoolExhausted(
                    f"request needs more pages than the whole pool "
                    f"holds ({self.engine.pool.num_pages - 1} usable)"
                    " — raise serve_gen_kv_pages or lower "
                    "max_new_tokens"))
                continue
            if not ok:
                self._pending.appendleft(req)
                self.metrics.counter(
                    "gen_admission_deferrals_total").inc()
                return
            # lowest free slot first: deterministic assignment (chaos
            # specs name slots; staggered-parity runs reproduce)
            self._admit_one(req, now)

    def _maybe_complete(self, req: _GenRequest, tok: int) -> None:
        eos = self.engine.eos_id
        if eos is not None and tok == eos:
            self._finish(req, "eos")
        elif req.n_generated >= req.max_new:
            if req.truncated_by_budget:
                self._finish(req, "budget", DeadlineExceeded(
                    f"token budget exhausted after {req.n_generated} "
                    "tokens (server cap serve_gen_token_budget/"
                    "max_seq room below the requested "
                    "max_new_tokens) — stream truncated"))
            else:
                self._finish(req, "length")

    def _sweep(self) -> None:
        """Client cancels + wall deadlines, checked at step boundaries
        so a mid-stream failure is typed and immediate."""
        now = time.monotonic()
        for slot in list(self._by_slot):
            req = self._by_slot[slot]
            if req.stream._cancel_requested:
                self._finish(req, "cancelled", StreamCancelled(
                    f"cancelled after {req.n_generated} tokens"))
            elif req.deadline is not None and now > req.deadline:
                self._finish(req, "deadline", DeadlineExceeded(
                    f"wall deadline exceeded mid-stream after "
                    f"{req.n_generated} tokens"))

    # -- main loop ----------------------------------------------------------

    def run(self) -> None:  # hot-path: the decode loop
        m = self.metrics
        slots = self.engine.slots
        try:
            # hot section for the sanitizer's sync accounting: every
            # readback on this thread attributes to the decode loop
            with jit_sanitizer.hot_section("gen_decode_loop"):
                self._run_loop(m, slots)
        except BaseException as e:  # noqa: broad-except — the loop
            # thread must record ANY death and resolve every stream
            # typed rather than leave clients blocked mid-iteration
            self.fatal = e
            err = RuntimeError(f"generation loop died: {e!r}")
            self._fail_inflight(err)
            self._fail_queued(err)
            self.drain.set()
            try:
                core_health.report_unhealthy(
                    f"generation loop died: {e!r}")
            except Exception:  # noqa: broad-except — best-effort
                # marker; the fatal must not be masked by an
                # unwritable health dir
                pass
            if not isinstance(e, Exception):
                raise
        finally:
            self.drained.set()
            # close the admission race for good: a submit whose put
            # landed after this loop's final empty-queue check is
            # either swept HERE (put before the sweep) or sees
            # drained already set on its own post-put check (put
            # after the sweep — drained.set() above happened-before
            # it) and sweeps itself. Normal drains flushed the queue
            # already, so this is a no-op for them.
            self._fail_queued(ServerClosed(
                "generation server drained while the request was "
                "being admitted"))

    def _maybe_release_chaos_pages(self) -> None:
        """Let go of gen_page_pressure chaos holds once their tick
        window passed (or immediately under drain/abort, so parked
        streams can complete and kv_pages_owed lands at 0)."""
        if self._chaos_pages and (
                self._tick >= self._chaos_release_tick
                or self.drain.is_set() or self._abort_exc is not None):
            self.engine.pool.release(self._chaos_pages)
            self._chaos_pages = []

    def _run_loop(self, m, slots: int) -> None:  # hot-path: decode loop
        while True:
            core_health.beat()
            self._tick += 1
            if self.engine.paged:
                self._maybe_release_chaos_pages()
            if self._abort_exc is not None:
                self._fail_inflight(self._abort_exc)
                self._fail_queued(self._abort_exc)
                break
            self._sweep()
            self._admit()
            if not self._by_slot:
                m.gauge("slot_occupancy").set(0.0)
                if (self.drain.is_set() and self.q.empty()
                        and not self._parked and not self._pending):
                    break
                time.sleep(self._POLL_S)
                continue
            if (self.engine.paged and core_chaos.enabled()
                    and core_chaos.check_gen_pressure()):
                # claim every free page and squat for ~25 ticks: the
                # deterministic trigger for the preemption path
                free = self.engine.pool.free_pages
                if free:
                    self._chaos_pages.extend(
                        self.engine.pool.alloc(free))
                self._chaos_release_tick = self._tick + 25
                obs_events.emit("gen_page_pressure",
                                pages_held=len(self._chaos_pages))
            wedged, slow = core_chaos.check_gen_step(
                list(self._by_slot))
            if slow:
                time.sleep(float(
                    core_flags.flag("serve_chaos_slow_s")))
            if wedged is not None and wedged in self._by_slot:
                req = self._by_slot[wedged]
                self._finish(req, "error", SlotWedged(
                    f"decode slot {wedged} wedged after "
                    f"{req.n_generated} tokens (chaos "
                    "gen_slot_wedge) — stream failed, slot "
                    "released, cohabitants unaffected"))
            if not self._by_slot:
                continue
            active = np.zeros([slots], bool)
            for slot, req in self._by_slot.items():
                active[slot] = req.stream._writable()
            m.gauge("slot_occupancy").set(
                len(self._by_slot) / slots)
            if not active.any():
                time.sleep(self._POLL_S)  # every stream is parked
                continue
            eng = self.engine
            drafts = np.zeros([slots, eng.spec_tokens], np.int32)
            nd = np.zeros([slots], np.int32)
            if eng.spec_tokens > 0:
                for slot, req in self._by_slot.items():
                    if active[slot] and req.spec is not None:
                        d = req.spec.propose()
                        nd[slot] = d.size
                        drafts[slot, :d.size] = d
            t0 = time.monotonic()
            toks, flags = eng.decode(active, drafts, nd)
            dt = time.monotonic() - t0
            m.histogram("decode_step_ms").observe(dt * 1e3)
            # a page fault the pool could not serve, handled at this
            # step boundary (the slot was masked out of the dispatch;
            # cohabitants decoded normally): preempt off = fail THAT
            # stream typed; preempt on = shed pressure instead
            # (prefix cache already LRU-shed inside pool.alloc, then
            # lowest-priority/longest-deadline victim parks, else the
            # faulting stream itself parks) — never client-visible
            for slot, exc in eng.last_page_faults.items():
                self._handle_fault(slot, exc)
            from ..obs import trace as obs_trace
            if obs_trace.sink_active():
                # decode spans tag slot occupancy: the trace view
                # shows continuous batching fill alongside timing
                obs_trace.record_span(
                    "gen/decode_step", dt, cat="Serving",
                    args={"slots_active": int(active.sum()),
                          "occupancy": round(
                              len(self._by_slot) / slots, 4)})
            for slot in list(self._by_slot):
                if not active[slot]:
                    continue
                req = self._by_slot[slot]
                n_acc = int(flags[slot].sum())
                if eng.spec_tokens > 0 and nd[slot] > 0:
                    self._spec_proposed += int(nd[slot])
                    self._spec_accepted += max(n_acc - 1, 0)
                    m.counter("gen_spec_proposed_total").inc(
                        int(nd[slot]))
                    m.counter("gen_spec_accepted_total").inc(
                        max(n_acc - 1, 0))
                    m.gauge("gen_spec_accept_ratio").set(
                        self._spec_accepted
                        / max(self._spec_proposed, 1))
                # flags[slot] is a prefix: every accepted chain entry
                # is a real token, delivered in order; eos/length can
                # retire the request mid-window (extras are discarded
                # — the slot's pages release with it)
                for i in range(n_acc):
                    tok = int(toks[slot, i])
                    if req.spec is not None:
                        req.spec.observe(tok)
                    self._deliver(req, tok)
                    self._maybe_complete(req, tok)
                    if req.slot < 0:
                        break
            eng.publish_kv_metrics()
            if self._debug_refcount:
                # per-tick accounting sweep: sum-of-refcounts == refs
                # held by live slots + registry (+ chaos holds), typed
                # KVPageAccountingError AT the corrupting tick
                eng.check_kv_invariants(
                    extra_holders=(self._chaos_pages,))


# kept for parity tests/bench: eagerly decode ONE sequence with the
# concat-Cache path but the ENGINE's key schedule, so sampled outputs
# are comparable token-for-token with the jitted slot decode
def eager_generate(model, prompt_ids, max_new_tokens, eos_id=None,
                   temperature=0.0, top_k=0, seed=0):
    """Reference eager decode (one sequence, incremental concat cache):
    prefill the prompt, then sample a token per step with the same
    per-request key schedule the engine uses. Returns the token list."""
    import jax
    from ..core.tensor import to_tensor
    from ..nn.decode import sample_logits_array
    prompt = np.asarray(prompt_ids, np.int64).reshape(1, -1)
    cache = model.empty_cache(1)
    logits, cache = model(to_tensor(prompt), cache=cache)
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF), 0)
    out: List[int] = []
    last = np.asarray(logits.numpy())[0, -1].astype(np.float32)
    for _ in range(int(max_new_tokens)):
        tok = int(np.asarray(sample_logits_array(
            last, jax.random.fold_in(key, 0),
            np.float32(temperature), np.int32(top_k))))
        key = jax.random.fold_in(key, 1)
        out.append(tok)
        if eos_id is not None and tok == eos_id:
            break
        if len(out) >= int(max_new_tokens):
            break
        ids = np.asarray([[tok]], np.int64)
        logits, cache = model(to_tensor(ids), cache=cache)
        last = np.asarray(logits.numpy())[0, -1].astype(np.float32)
    return out
