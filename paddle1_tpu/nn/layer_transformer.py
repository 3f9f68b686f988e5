"""Transformer layers.

Analog of python/paddle/nn/layer/transformer.py in the reference
(MultiHeadAttention:109, TransformerEncoderLayer:431, TransformerEncoder:607,
TransformerDecoderLayer/Decoder, full Transformer:1088).

TPU-native notes: attention goes through
nn.functional.scaled_dot_product_attention (flash/Pallas-eligible); the
Q/K/V projections are separate Linears like the reference (fusable by XLA);
caches use the reference's (k, v) namedtuple protocol for incremental
decoding.
"""

from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..core.errors import InvalidArgumentError
from ..core.recompute_keeps import keep_in_recompute
from . import functional as F
from .layer_base import Layer
from .layer_common import Dropout, Linear
from .layer_norm_act import LayerNorm, LayerList

__all__ = ["MultiHeadAttention", "GatedFeedForward", "PlainFeedForward",
           "TransformerEncoderLayer", "TransformerEncoder",
           "TransformerDecoderLayer", "TransformerDecoder", "Transformer"]


def _convert_attention_mask(attn_mask, dtype):
    """bool mask (True=keep) → additive; int mask → additive (reference
    transformer.py _convert_attention_mask)."""
    if attn_mask is None:
        return None
    from ..ops import manip_ops, math_ops
    from ..core import dtype as dtypes
    if attn_mask.dtype == dtypes.bool_ or str(attn_mask.dtype).startswith("int"):
        from ..autograd.engine import apply
        import jax.numpy as jnp

        def f(m):
            keep = m.astype(bool)
            return jnp.where(keep, 0.0, -1e9).astype(dtypes.convert_dtype(dtype))
        return apply("convert_mask", f, (attn_mask,))
    return attn_mask


class MultiHeadAttention(Layer):
    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])
    # Serving decode cache (ISSUE 9): preallocated [slots, max_seq,
    # heads, dim] K/V written in place at a per-slot cursor ``pos``
    # ([slots] int32, tokens already written) via dynamic_update_slice.
    # Unlike ``Cache`` — whose per-step concat grows the K/V shape, so
    # every decode step is O(written) copy work AND a fresh trace — the
    # GenCache shapes never change: one compiled decode executable
    # serves every step of every sequence, and the write is O(new
    # tokens). Rows at/past a slot's cursor hold stale garbage; the
    # caller masks them (keys j <= pos+i) and the cursor overwrites
    # them as it advances.
    GenCache = collections.namedtuple("GenCache", ["k", "v", "pos"])
    # Block-paged serving decode cache (ISSUE 16): k/v are GLOBAL pools
    # of fixed-size pages — [pages, heads, page_size, dim], heads ahead
    # of the page rows so the Pallas gather can block one (page, head)
    # tile — shared by every slot, with ``table`` ([slots, max_pages_per_slot] int32)
    # mapping each slot's logical positions onto pool pages and ``pos``
    # the same per-slot cursor GenCache carries. A slot's HBM footprint
    # is ceil(len/page_size) pages instead of max_seq rows, and slots
    # over a common prompt can alias the same full prefill pages
    # (refcounted host-side, serving/paging.py). Table rows point at the
    # reserved parking page 0 beyond a slot's allocation, so free slots
    # ride the same dispatch writing only parking garbage. Shapes never
    # change: the one-compile decode contract survives paging.
    PagedCache = collections.namedtuple("PagedCache",
                                        ["k", "v", "table", "pos"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None, fuse_qkv=False):
        super().__init__()
        if embed_dim % num_heads != 0:
            raise InvalidArgumentError(
                "embed_dim must be divisible by num_heads")
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        from ..ops import manip_ops
        b, n = x.shape[0], x.shape[1]
        return manip_ops.reshape(x, [b, n, self.num_heads, self.head_dim])

    def _prepare_qkv(self, query, key, value, cache=None):
        from ..ops import manip_ops
        q = self._split_heads(self.q_proj(query))
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value))
        if isinstance(cache, self.PagedCache):
            from ..autograd.engine import apply
            import jax.numpy as jnp

            def write(pool, new, table, p):
                # scatter slot s's new [W, H, D] window into its pages:
                # logical position i lives at page table[s, i//ps],
                # offset i%ps. Beyond-allocation positions resolve to
                # the parking page (table rows are parking-filled), so
                # free/overflowing slots only scribble parking garbage;
                # the min() clamp keeps the page-table gather in range
                # for cursors past capacity.
                ps = pool.shape[2]
                w = new.shape[1]
                idx = p[:, None] + jnp.arange(w, dtype=p.dtype)[None, :]
                idx = jnp.minimum(idx, table.shape[1] * ps - 1)
                pg = jnp.take_along_axis(table, idx // ps, axis=1)
                # the two index arrays straddle the heads slice, so the
                # indexed result is [S, W, H, D] — ``new``'s own layout
                return pool.at[pg, :, idx % ps].set(
                    new.astype(pool.dtype))

            k = apply("paged_cache_write_k", write,
                      (cache.k, k, cache.table, cache.pos))
            v = apply("paged_cache_write_v", write,
                      (cache.v, v, cache.table, cache.pos))
            new_tokens = query.shape[1]
            pos = apply("gen_cache_advance",
                        lambda p: p + np.int32(new_tokens), (cache.pos,))
            cache = self.PagedCache(k, v, cache.table, pos)
        elif isinstance(cache, self.GenCache):
            from ..autograd.engine import apply
            import jax

            def write(c, n, p):
                # per-slot in-place write: row s gets its new [L, H, D]
                # block at cursor p[s]. dynamic_update_slice clamps the
                # start so an (engine-prevented) overflow can only
                # corrupt the writing slot's own row, never a neighbor.
                def one(row, new, pos):
                    return jax.lax.dynamic_update_slice(
                        row, new.astype(row.dtype), (pos, 0, 0))
                return jax.vmap(one)(c, n, p)

            k = apply("gen_cache_write_k", write, (cache.k, k, cache.pos))
            v = apply("gen_cache_write_v", write, (cache.v, v, cache.pos))
            new_tokens = query.shape[1]
            pos = apply("gen_cache_advance",
                        lambda p: p + np.int32(new_tokens), (cache.pos,))
            cache = self.GenCache(k, v, pos)
        elif isinstance(cache, self.Cache):
            k = manip_ops.concat([cache.k, k], axis=1)
            v = manip_ops.concat([cache.v, v], axis=1)
            cache = self.Cache(k, v)
        return q, k, v, cache

    def gen_cache(self, key, value=None, type=Cache):
        from ..ops import manip_ops
        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None
                                              else key))
            return self.StaticCache(k, v)
        b = key.shape[0]
        from ..ops import manip_ops as mo
        k = mo.zeros([b, 0, self.num_heads, self.head_dim], "float32")
        v = mo.zeros([b, 0, self.num_heads, self.head_dim], "float32")
        return self.Cache(k, v)

    def gen_slot_cache(self, slots, max_seq, dtype="float32"):
        """Preallocated serving decode cache: ``slots`` independent
        sequences, each owning one ``[max_seq, heads, dim]`` K/V row
        written at its own cursor (see :attr:`GenCache`). The arrays
        never change shape, so the decode step compiles exactly once."""
        from ..ops import manip_ops as mo
        shape = [int(slots), int(max_seq), self.num_heads, self.head_dim]
        return self.GenCache(mo.zeros(shape, dtype),
                             mo.zeros(shape, dtype),
                             mo.zeros([int(slots)], "int32"))

    def gen_paged_cache(self, pages, page_size, dtype="float32"):
        """Block-paged serving decode cache: global K/V pools of
        ``pages`` fixed-size pages (see :attr:`PagedCache`). The
        returned ``table``/``pos`` are 1-element placeholders — the
        engine owns the real [slots, max_pages_per_slot] table and
        per-slot cursors and substitutes them per dispatch."""
        from ..ops import manip_ops as mo
        shape = [int(pages), self.num_heads, int(page_size),
                 self.head_dim]
        return self.PagedCache(mo.zeros(shape, dtype),
                               mo.zeros(shape, dtype),
                               mo.zeros([1, 1], "int32"),
                               mo.zeros([1], "int32"))

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None):
        key = query if key is None else key
        value = key if value is None else value
        q, k, v, cache = self._prepare_qkv(query, key, value, cache)
        if isinstance(cache, self.PagedCache):
            # masking is positional (keys <= cursor); any attn_mask is
            # ignored by contract — the paged engine passes None
            out = F.paged_attention(q, cache.k, cache.v, cache.table,
                                    cache.pos)
            from ..ops import manip_ops as _mo
            b, n = out.shape[0], out.shape[1]
            out = _mo.reshape(out, [b, n, self.embed_dim])
            out = self.out_proj(out)
            outs = [out]
            if self.need_weights:
                outs.append(None)
            outs.append(cache)
            return tuple(outs)
        from ..core import dtype as dtypes
        if attn_mask is not None and (
                attn_mask.dtype == dtypes.bool_ or
                str(attn_mask.dtype).startswith("int")):
            # keep the boolean form: sdpa consumes it exactly (and can
            # route the fused flash kernel under trace); the additive
            # conversion below stays for float masks / reference parity
            from ..autograd.engine import apply as _apply
            import jax.numpy as _jnp
            mask = attn_mask if attn_mask.dtype == dtypes.bool_ else \
                _apply("mask_to_bool", lambda m: m.astype(_jnp.bool_),
                       (attn_mask,))
        else:
            mask = _convert_attention_mask(attn_mask, q.dtype)
        if mask is not None:
            mask_arr = mask  # [B,H,Nq,Nk]-broadcastable additive mask
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask_arr,
                dropout_p=self.dropout, training=self.training)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, dropout_p=self.dropout, training=self.training)
        from ..ops import manip_ops
        b, n = out.shape[0], out.shape[1]
        out = manip_ops.reshape(out, [b, n, self.embed_dim])
        out = self.out_proj(out)
        outs = [out]
        if self.need_weights:
            outs.append(None)  # weights unavailable on the fused path
        if cache is not None:
            outs.append(cache)
        return out if len(outs) == 1 else tuple(outs)


@jax.custom_vjp
def _made_once(s):
    """``s`` behind an optimization barrier in the forward pass (none on
    its gradient): :class:`GatedFeedForward`'s note."""
    return jax.lax.optimization_barrier(s)


_made_once.defvjp(lambda s: (jax.lax.optimization_barrier(s), None),
                  lambda _, d: (d,))


class GatedFeedForward(Layer):
    """``down(silu(gate(x)) * up(x))``, the SwiGLU feed-forward of the
    decoder families after 2020; no reference analog. Three ``linear``
    ops and one ``swiglu`` in a trace."""

    def __init__(self, d_model, dim_feedforward, weight_attr=None,
                 bias_attr=False):
        super().__init__()
        self.gate_proj = Linear(d_model, dim_feedforward, weight_attr,
                                bias_attr)
        self.up_proj = Linear(d_model, dim_feedforward, weight_attr,
                              bias_attr)
        self.down_proj = Linear(dim_feedforward, d_model, weight_attr,
                                bias_attr)

    def forward(self, x):
        from ..autograd.engine import apply
        # the gated product is made once, as a value of its own: where a
        # recomputed segment keeps the output below, XLA otherwise makes
        # the product again inside the weight-gradient fusion of
        # ``down_proj``, which it defers, and holds the gate and up
        # outputs of every layer of a loop step until then (Ouro's step
        # compiled for a v5e: 7.99 GiB of temporaries against 6.59, and
        # the chip ran out; PERF.md, PR 37)
        s = apply("made_once", _made_once,
                  (F.swiglu(self.gate_proj(x), self.up_proj(x)),))
        # a recomputed segment keeps the output where its backward reads
        # it (a norm behind the feed-forward, Ouro's sandwich): the
        # product contracts over the wide side (K 5632 -> 2048: 1.03 ms
        # for 33.5 MB, 0.031 ms a MB on a v5e, PERF.md PR 37). Where
        # nothing reads it (a pre-norm block adds it to the stream) it
        # is not held
        return keep_in_recompute(self.down_proj(s), "gated_ffn_out")


class PlainFeedForward(Layer):
    """``down(act(up(x)))``, a feed-forward without a gate; ``activation``
    ``"relu2"`` (``relu(.)^2``, the ``nemotron_h`` family's), ``"relu"``
    or ``"silu"``. Two ``linear`` ops and one ``ffn_activation`` in a
    trace."""

    ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                   "relu2": lambda u: jnp.square(jax.nn.relu(u))}

    def __init__(self, d_model, dim_feedforward, activation="relu2",
                 weight_attr=None, bias_attr=False):
        super().__init__()
        self.act = self.ACTIVATIONS[activation]
        self.up_proj = Linear(d_model, dim_feedforward, weight_attr,
                              bias_attr)
        self.down_proj = Linear(dim_feedforward, d_model, weight_attr,
                                bias_attr)

    def forward(self, x):
        from ..autograd.engine import apply
        return self.down_proj(apply("ffn_activation", self.act,
                                    (self.up_proj(x),)))


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr,
                              bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr,
                              bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self.linear2(self.dropout(self.activation(self.linear1(src))))
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)

    def gen_slot_cache(self, slots, max_seq, dtype="float32"):
        return self.self_attn.gen_slot_cache(slots, max_seq, dtype)

    def gen_paged_cache(self, pages, page_size, dtype="float32"):
        return self.self_attn.gen_paged_cache(pages, page_size, dtype)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [encoder_layer] +
            [copy.deepcopy(encoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm
        # declared, so that ParallelEngine(recompute=True) finds it
        self.enable_recompute = False

    def forward(self, src, src_mask=None, cache=None):
        # In-graph pipeline parallelism: when the engine tagged this
        # encoder with a pp mesh axis (ParallelEngine degrees={"pp": n}),
        # the block stack runs as a scan+ppermute pipeline sharded over
        # that axis instead of a sequential loop. Decode caches and eager
        # calls keep the sequential path.
        if (getattr(self, "pipeline_axis", None) is not None and
                cache is None and
                isinstance(src.data if hasattr(src, "data") else src,
                           jax.core.Tracer)):
            out = self._forward_pipelined(src, src_mask)
            if self.norm is not None:
                out = self.norm(out)
            return out
        output = src
        new_caches = []
        # enable_recompute: per-block activation rematerialisation
        # (reference RecomputeOptimizer segments; paddlenlp sets the same
        # attribute) — real peak-memory reduction, unlike checkpointing
        # the whole loss.
        remat = getattr(self, "enable_recompute", False) and self.training
        for i, mod in enumerate(self.layers):
            if cache is None:
                if remat:
                    from ..distributed.fleet.utils.recompute import \
                        recompute
                    output = recompute(mod, output, src_mask)
                else:
                    output = mod(output, src_mask)
            else:
                output, new_cache = mod(output, src_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]

    def gen_slot_cache(self, slots, max_seq, dtype="float32"):
        """Per-layer preallocated slot caches for the serving decode
        engine (one :attr:`MultiHeadAttention.GenCache` per block)."""
        return [layer.gen_slot_cache(slots, max_seq, dtype)
                for layer in self.layers]

    def gen_paged_cache(self, pages, page_size, dtype="float32"):
        """Per-layer paged KV pools for the serving decode engine (one
        :attr:`MultiHeadAttention.PagedCache` per block; the engine owns
        the shared page table)."""
        return [layer.gen_paged_cache(pages, page_size, dtype)
                for layer in self.layers]

    def _forward_pipelined(self, src, src_mask=None):
        """Block stack as an in-graph pipeline over the ``pipeline_axis``
        mesh axis (SURVEY §7 hard part (b); reference SectionWorker
        1F1B, section_worker.cc:143-181).

        The batch splits into ``pipeline_microbatches`` microbatches; the
        per-stage block parameters are stacked on a leading axis sharded
        over pp; one lax.scan clocks every stage in SPMD with ppermute
        rotating activations along ICI (distributed/pipeline.py). Only the
        'pp' axis is manual in the shard_map — dp/mp/sharding stay under
        GSPMD, so the pipeline composes with the other parallelisms.
        Per-tick rematerialisation bounds live activations at one
        microbatch per stage.
        """
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from ..distributed.pipeline import pipeline_apply

        axis = self.pipeline_axis
        mesh = self.pipeline_mesh
        n_stages = int(mesh.shape[axis])
        n_micro = int(getattr(self, "pipeline_microbatches", 0) or n_stages)
        blocks = list(self.layers)
        if len(blocks) % n_stages:
            raise InvalidArgumentError(
                f"pipelined encoder: {len(blocks)} blocks not divisible "
                f"into {n_stages} stages")
        bps = len(blocks) // n_stages
        template = blocks[0]

        x = src.data if isinstance(src, Tensor) else jnp.asarray(src)
        b = x.shape[0]
        if b % n_micro:
            raise InvalidArgumentError(
                f"pipelined encoder: batch {b} not divisible by "
                f"{n_micro} microbatches")
        mb = b // n_micro
        micro_x = x.reshape((n_micro, mb) + x.shape[1:])

        mask_arr = None
        if src_mask is not None:
            mask_arr = src_mask.data if isinstance(src_mask, Tensor) \
                else jnp.asarray(src_mask)
            if mask_arr.ndim >= 1 and mask_arr.shape[0] == b:
                # per-example mask: split along batch with the microbatches
                micro_mask = mask_arr.reshape((n_micro, mb) +
                                              mask_arr.shape[1:])
            else:
                # broadcastable mask ([1,1,S,S], [S,S], ...): identical for
                # every microbatch — replicate on the leading micro axis
                micro_mask = jnp.broadcast_to(
                    mask_arr[None], (n_micro,) + mask_arr.shape)

        # [n_stages, bps, ...] per leaf — differentiable stack, so grads
        # flow back to each block's own parameters
        block_sds = [blk.state_dict() for blk in blocks]
        keys = list(block_sds[0].keys())
        stacked = {
            k: jnp.stack([
                jnp.stack([block_sds[s * bps + i][k].data
                           for i in range(bps)])
                for s in range(n_stages)])
            for k in keys}

        def stage_fn(sp, xx, aux=None):
            t = Tensor(xx)
            m = None if aux is None else Tensor(aux)
            for i in range(bps):
                blk_params = {k: v[i] for k, v in sp.items()}
                with template.load_functional_state(blk_params):
                    t = template(t, m)
            return t.data if isinstance(t, Tensor) else t

        in_specs = [{k: P(axis) for k in keys}, P()]
        args = [stacked, micro_x]
        if mask_arr is not None:
            body = lambda sp, mi, mm: pipeline_apply(
                stage_fn, sp, mi, axis, micro_aux=mm)
            in_specs.append(P())
            args.append(micro_mask)
        else:
            body = lambda sp, mi: pipeline_apply(stage_fn, sp, mi, axis)
        out = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=P(), axis_names=frozenset({axis}),
                        check_vma=False)(*args)
        out = out.reshape((b,) + out.shape[2:])
        return Tensor(out)  # traced-only path: the tape is off here


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incremental_cache = None
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                                    cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
            static_cache = None
        else:
            tgt, static_cache = self.cross_attn(tgt, memory, memory,
                                                memory_mask, cache[1])
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incremental_cache,
                                                static_cache))

    def gen_cache(self, memory):
        incremental = self.self_attn.gen_cache(memory,
                                               type=MultiHeadAttention.Cache)
        static = self.cross_attn.gen_cache(
            memory, memory, type=MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [decoder_layer] +
            [copy.deepcopy(decoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask,
                                        memory_mask, cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


class Transformer(Layer):
    """Full encoder-decoder transformer (reference transformer.py:1088)."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            encoder_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            encoder_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(encoder_layer,
                                              num_encoder_layers,
                                              encoder_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            decoder_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            decoder_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(decoder_layer,
                                              num_decoder_layers,
                                              decoder_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    def generate_square_subsequent_mask(self, length):
        from ..ops import manip_ops
        import numpy as np
        m = np.triu(np.full((length, length), -np.inf, np.float32), 1)
        from ..core.tensor import to_tensor
        return to_tensor(m)
