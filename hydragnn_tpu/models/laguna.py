"""Laguna: a sparse language model as the tenth stack.

A document is a graph, a token a node, the nodes of a graph contiguous
(graph/batch.py collate); node ``i``'s position is its index inside its
graph.  The node input is an integer id (``g.x[:, 0]``, exact in float32)
looked up in the held slice of the embedding; the "conv" is a pre-norm
block of gated grouped-query attention over each graph's nodes
(ops/attention.py, the edge set implicit) and a dense or mixture-of-experts
feed-forward (ops/moe.py); the node head is ONE untied matrix over the
held vocabulary rows, and the loss is ``softmax_xent`` against node
``i+1``'s id (models/layers.py).

The equations are poolside/Laguna-S-2.1's ``config.json`` (``model_type``
laguna) as models/laguna_reference.py writes them down; that file is the
independent float32 reference the tests and the benchmark hold this stack
to, and it names the forms the config is silent on (``ASSUMED``).  The
chip's share of the layer (experts, heads, vocabulary rows held) comes as
a ``LayerShare`` (parallel/share.py).

Precision: parameters float32.  With ``compute_dtype: bfloat16`` the
matrix products take bfloat16 operands and accumulate in float32; the
residual stream, the norms, the rotary angles, the router (HIGHEST), the
softmaxes and the loss stay float32.  This stack casts for itself
(``casts_at_boundary = False``): the trainer's boundary cast would round
the router and the ids.  Each half of a layer (attention; feed-forward) is
recomputed in the backward pass from its input and from the few arrays its
checkpoint keeps by name because running them again is dear (the attention
kernel's result, log-sum-exp and operands: ops/attention.py ``KEEP_ATTN``;
the router's decision: ops/moe.py ``KEEP_ROUTE``; the dense feed-forward's
two hidden products: ``KEEP_FFN`` below), the dense feed-forward in
``DENSE_CHUNKS`` node slices: beside 16 bytes a parameter of weights,
gradients and AdamW moments there is room for one half-layer's activations
and those arrays, not for five layers'.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.laguna_reference import apply_rotary
from hydragnn_tpu.ops.attention import (
    KEEP_ATTN,
    graph_attention,
    kept_mb,
    named_mb,
    scheduled_blocks,
)
from hydragnn_tpu.ops.moe import KEEP_ROUTE, routed_experts
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.utils.scope import phase

_ROPE_KEYS = ("rope_type", "rope_theta", "partial_rotary_factor", "factor",
              "original_max_position_embeddings", "beta_fast", "beta_slow",
              "attention_factor")


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The sizes held HERE, hashable (``Architecture.laguna``)."""

    hidden_size: int
    head_dim: int
    vocab_size: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_key_value_heads: int
    sliding_window: int
    rms_norm_eps: float
    norm_topk_prob: bool
    moe_routed_scaling_factor: float
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads_per_layer: Tuple[int, ...]
    rope: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]
    max_graph_nodes: Optional[int] = None
    router_scoring: ClassVar[str] = "softmax"     # ops/moe.py route

    @staticmethod
    def from_arch(arch: Dict[str, Any]) -> "LagunaConfig":
        lm = arch["laguna"]
        n = int(lm["num_hidden_layers"])
        # forms of the family this stack does not compute
        for key, want in (("gating", "per-head"),
                          ("moe_router_logit_softcapping", 0),
                          ("moe_apply_router_weight_on_input", False),
                          ("attention_bias", False)):
            if lm.get(key, want) != want:
                raise ValueError(
                    f"Laguna: {key}={lm[key]!r} is not implemented")
        return LagunaConfig(
            hidden_size=int(lm["hidden_size"]), head_dim=int(lm["head_dim"]),
            vocab_size=int(lm["vocab_size"]),
            intermediate_size=int(lm["intermediate_size"]),
            moe_intermediate_size=int(lm["moe_intermediate_size"]),
            shared_expert_intermediate_size=int(
                lm["shared_expert_intermediate_size"]),
            num_experts=int(lm["num_experts"]),
            num_experts_per_tok=int(lm["num_experts_per_tok"]),
            num_key_value_heads=int(lm["num_key_value_heads"]),
            sliding_window=int(lm["sliding_window"]),
            rms_norm_eps=float(lm["rms_norm_eps"]),
            norm_topk_prob=bool(lm.get("norm_topk_prob", True)),
            moe_routed_scaling_factor=float(
                lm.get("moe_routed_scaling_factor", 1.0)),
            layer_types=tuple(lm["layer_types"][:n]),
            mlp_layer_types=tuple(lm["mlp_layer_types"][:n]),
            num_attention_heads_per_layer=tuple(
                int(h) for h in lm["num_attention_heads_per_layer"][:n]),
            rope=tuple(sorted(
                (kind, tuple(sorted((k, v) for k, v in r.items()
                                    if k in _ROPE_KEYS)))
                for kind, r in lm["rope_parameters"].items())),
            max_graph_nodes=arch.get("max_graph_nodes"))

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def rope_of(self, kind: str) -> Dict[str, Any]:
        return dict(dict(self.rope)[kind])


def _dot(x, w, dtype, out=jnp.float32):
    """Operands in ``dtype``, float32 accumulation, result in ``out``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out)


def _rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _gated_mlp(u, w1, w3, w2, dtype, names=None):
    """The hidden products leave the MXU rounded to ``dtype`` (float32
    accumulation inside): at width 12288 a float32 hidden is 1 GB.
    ``names``: what to bind the two under for a checkpoint's policy (the
    dense feed-forward's; the shared experts bind nothing)."""
    h1, h3 = _dot(u, w1, dtype, dtype), _dot(u, w3, dtype, dtype)
    if names:
        h1, h3 = checkpoint_name(h1, names[0]), checkpoint_name(h3, names[1])
    h = (jax.nn.silu(h1.astype(jnp.float32)) * h3).astype(dtype)
    return _dot(h, w2, dtype)


def _in_chunks(fn, u, chunks, policy=None):
    """``fn`` over ``chunks`` slices of the node axis, one at a time and
    each recomputed in the backward pass: a wide hidden layer then lives
    for one slice only.  What ``policy`` keeps of a slice is not
    recomputed and lives for the whole step.  Under a policy the loop over
    the slices is unrolled: as a loop it would hand the kept arrays on,
    stacked, as loop state, and of a loop inside the scanned train step the
    TPU compiler reserves that state twice (1.1 GB kept cost 2.2 GB at
    23,512 nodes: PERF.md section 6, PR 42).  ``chunks`` must divide the
    node count."""
    if chunks <= 1 or u.shape[0] % chunks:
        return fn(u)
    piece = jax.checkpoint(fn, policy=policy)
    _, out = jax.lax.scan(
        lambda _, x: ((), piece(x)), (),
        u.reshape(chunks, u.shape[0] // chunks, u.shape[1]),
        unroll=policy is not None)
    return out.reshape(u.shape[0], out.shape[-1])


DENSE_CHUNKS = 4     # node slices of the dense feed-forward

# What the checkpoint of a dense feed-forward's slice keeps where the layer
# hands ``DenseFFN`` this policy: the two up-products, [N, intermediate] in
# the compute dtype each once the slices are stacked.  With them kept a
# recomputed slice runs the norm and the elementwise gate (whose float32
# temporaries still live one slice at a time) and neither product: two of
# the half's eight wide products a step (1.15e12 FLOP each at 15,168 nodes,
# 3072 -> 12288).  Whether a stack keeps them is its layer's to say, by the
# memory its step has left.
FFN_H1, FFN_H3 = "ffn.dense.h1", "ffn.dense.h3"
KEEP_FFN = jax.checkpoint_policies.save_only_these_names(FFN_H1, FFN_H3)


def where_narrow(policy, dtype):
    """``policy`` where the products leave the MXU in 2 bytes a value
    (``dtype`` bfloat16), else None: a wide product's result is worth its
    room at that size only.  In float32 the same arrays are twice the
    bytes: one float32 forward and backward pass at 23,512 nodes, five
    layers, would need 17.3 GB with them and needs 12.7 without (the
    device has 16.9; PERF.md section 6, PR 42)."""
    return policy if jnp.dtype(dtype).itemsize <= 2 else None


def _init(fan_in):
    return nn.initializers.normal(stddev=fan_in ** -0.5)


class LagunaLayer(nn.Module):
    lm: LagunaConfig
    share: LayerShare
    layer: int
    dtype: Any
    attention_backend: Optional[str] = None
    moe_backend: Optional[str] = None
    interpret: bool = False

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions):
        """(x after both halves, routing stats or None, attention's
        scheduled blocks, the MB the feed-forward half's checkpoints keep:
        ``{"ffn": MB}`` on a dense layer, else {}).  The attention half and
        the feed-forward half are each recomputed in the backward pass from
        their input: the dense feed-forward slice by slice (DenseFFN) and,
        in bfloat16, from its two kept up-products (KEEP_FFN, where_narrow:
        0.75 GB at 15,168 nodes, where the step needs 12.5 of the device's
        16.9 GB); the expert half also from its router's kept decision
        (ops/moe.py KEEP_ROUTE); the attention half also from the kernel's
        kept result and log-sum-exp and from q, k, v, one key/value head
        beside 6 or 9 query heads (ops/attention.py KEEP_ATTN), so its
        backward pass runs no forward kernel, rotary or cast again."""
        lm = self.lm
        kind = lm.layer_types[self.layer]
        heads = lm.num_attention_heads_per_layer[self.layer]
        kv = lm.num_key_value_heads
        a, blocks = nn.remat(Attention, policy=KEEP_ATTN)(
            lm, kind, heads, kv, self.dtype, self.attention_backend,
            self.interpret, name="attn")(x, node_gid, node_mask, positions)
        h = x + a
        if lm.mlp_layer_types[self.layer] == "dense":
            ffn = DenseFFN(lm, self.dtype,
                           where_narrow(KEEP_FFN, self.dtype), name="ffn")
            return h + ffn(h), None, blocks, {"ffn": ffn.kept_mb(h)}
        y, stats = nn.remat(MoE, policy=KEEP_ROUTE)(
            lm, self.share, self.dtype, self.moe_backend, self.interpret,
            name="moe")(h, node_mask)
        return h + y, stats, blocks, {}


class Attention(nn.Module):
    lm: LagunaConfig
    kind: str
    heads: int
    kv: int
    dtype: Any
    backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions):
        lm, d, hd = self.lm, self.lm.hidden_size, self.lm.head_dim
        n = x.shape[0]
        norm = self.param("norm", nn.initializers.ones, (d,))
        wq = self.param("wq", _init(d), (d, self.heads * hd))
        wk = self.param("wk", _init(d), (d, self.kv * hd))
        wv = self.param("wv", _init(d), (d, self.kv * hd))
        wg = self.param("wg", _init(d), (d, self.heads))
        wo = self.param("wo", _init(self.heads * hd), (self.heads * hd, d))
        rope = lm.rope_of(self.kind)
        with phase("attn.proj"):
            u = _rms_norm(x, norm, lm.rms_norm_eps)
            # the rotation is the reference's own function (float32
            # angles; the inverse frequencies are constants of the config)
            q = apply_rotary(
                _dot(u, wq, self.dtype).reshape(n, self.heads, hd),
                positions, rope, hd).astype(self.dtype)
            k = apply_rotary(
                _dot(u, wk, self.dtype).reshape(n, self.kv, hd),
                positions, rope, hd).astype(self.dtype)
            v = _dot(u, wv, self.dtype).reshape(
                n, self.kv, hd).astype(self.dtype)
            gate = jax.nn.sigmoid(_dot(u, wg, self.dtype))
        window = (lm.sliding_window if self.kind == "sliding_attention"
                  else None)
        o = graph_attention(q, k, v, node_gid, node_mask, window=window,
                            max_span=lm.max_graph_nodes,
                            backend=self.backend, interpret=self.interpret)
        blocks = (*scheduled_blocks(node_gid, node_mask, window=window,
                                    max_span=lm.max_graph_nodes),
                  kept_mb(q, k, v, KEEP_ATTN, backend=self.backend))
        with phase("attn.proj"):
            o = o.astype(jnp.float32) * gate[:, :, None]
            return _dot(o.reshape(n, self.heads * hd), wo,
                        self.dtype), blocks


class DenseFFN(nn.Module):
    """``policy``: what each slice's checkpoint keeps (``KEEP_FFN``, or
    None: a slice is recomputed from its input alone)."""

    lm: LagunaConfig
    dtype: Any
    policy: Any = None

    @nn.compact
    def __call__(self, h):
        d, f = self.lm.hidden_size, self.lm.intermediate_size
        norm = self.param("norm", nn.initializers.ones, (d,))
        w1 = self.param("w1", _init(d), (d, f))
        w3 = self.param("w3", _init(d), (d, f))
        w2 = self.param("w2", _init(f), (f, d))
        def ffn(hs):
            u = _rms_norm(hs, norm, self.lm.rms_norm_eps)
            # named only where a checkpoint asks: a bare name leaves the
            # program as it was but for the numbering of its functions,
            # which is enough to miss the compile cache
            names = (FFN_H1, FFN_H3) if self.policy else None
            return _gated_mlp(u, w1, w3, w2, self.dtype, names)

        with phase("ffn.dense"):
            return _in_chunks(ffn, h, DENSE_CHUNKS, self.policy)

    def kept_mb(self, h):
        """MB (1e6 bytes) the slices' checkpoints keep of ``h``'s rows in
        one step, by asking the policy for each name: a number of the
        shapes alone, 0 under no policy."""
        hidden = jax.ShapeDtypeStruct(
            (h.shape[0], self.lm.intermediate_size), self.dtype)
        return named_mb(self.policy, {FFN_H1: hidden, FFN_H3: hidden})


class MoE(nn.Module):
    lm: LagunaConfig
    share: LayerShare
    dtype: Any
    backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, h, node_mask, bias=None):
        """``bias`` [E]: the router's correction bias, where the model has
        one (models/glm_moe_lite.py); the stats then carry ``counts_all``."""
        lm, share, d = self.lm, self.share, self.lm.hidden_size
        f, fs = lm.moe_intermediate_size, lm.shared_expert_intermediate_size
        e = share.experts_held
        norm = self.param("norm", nn.initializers.ones, (d,))
        router = self.param("router", _init(d),
                            (d, share.num_experts_total))
        w1 = self.param("experts_w1", _init(d), (e, d, f))
        w3 = self.param("experts_w3", _init(d), (e, d, f))
        w2 = self.param("experts_w2", _init(f), (e, f, d))
        s1 = self.param("shared_w1", _init(d), (d, fs))
        s3 = self.param("shared_w3", _init(d), (d, fs))
        s2 = self.param("shared_w2", _init(fs), (fs, d))
        u = _rms_norm(h, norm, lm.rms_norm_eps)
        y, stats = routed_experts(
            u, router, w1, w3, w2, share, node_mask=node_mask,
            top_k=lm.num_experts_per_tok, norm_topk=lm.norm_topk_prob,
            scale=lm.moe_routed_scaling_factor, scoring=lm.router_scoring,
            bias=bias, compute_dtype=self.dtype, backend=self.backend,
            interpret=self.interpret)
        with phase("moe.shared"):
            return y + _gated_mlp(u, s1, s3, s2, self.dtype), stats


class LagunaStack(nn.Module):
    """``cfg.lm`` / ``cfg.share`` carry the model; the trainer's contract
    is the other stacks': ``model.apply(variables, batch, train=...)`` ->
    a tuple with one output per head (here the logits [N, V held])."""

    cfg: Any
    attention_backend: Optional[str] = None
    moe_backend: Optional[str] = None
    interpret: bool = False

    # trainer._loss_and_metrics: no bf16 cast of params and ids for us
    casts_at_boundary = False
    # trainer.create_train_state: shape the parameters under jit
    jit_init = True
    # telemetry bind_step: no in-run MFU estimate (XLA's cost model does
    # not see inside the attention and grouped-product kernels)
    cost_model_sees_flops = False

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        lm, share = self.cfg.lm, self.cfg.share
        dtype = (jnp.bfloat16 if self.cfg.compute_dtype == "bfloat16"
                 else jnp.float32)
        embed = self.param("embed", nn.initializers.normal(stddev=1.0),
                           (share.vocab_rows, lm.hidden_size))
        with phase("lm.embed"):
            ids, positions = ids_and_positions(g, share)
            x = jnp.take(embed, ids, axis=0)
        stats, blocks, kept = [], [], []
        for layer in range(lm.num_layers):
            x, s, b, m = LagunaLayer(
                lm, share, layer, dtype, self.attention_backend,
                self.moe_backend, self.interpret, name=f"layer_{layer}")(
                    x, g.node_gid, g.node_mask, positions)
            blocks.append(b)
            kept.append(m)
            if s is not None:
                stats.append(s)
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (lm.hidden_size,))
        head = self.param("head", _init(lm.hidden_size),
                          (lm.hidden_size, share.vocab_rows))
        with phase("lm.head"):
            logits = _dot(_rms_norm(x, final_norm, lm.rms_norm_eps), head,
                          dtype)
        count_routing(self, stats, train)
        count_blocks(self, blocks, train)
        count_kept(self, kept, train, "ffn")
        return (logits,)


def ids_and_positions(g: GraphBatch, share: LayerShare):
    """(row of the held embedding slice, position) of every node."""
    ids = jnp.clip(g.x[:, 0].astype(jnp.int32) - share.vocab_offset,
                   0, share.vocab_rows - 1)
    # a node's position is its index inside its graph: graphs are
    # contiguous, so it is the distance to the graph's first node
    idx = jnp.arange(g.num_nodes, dtype=jnp.int32)
    first = jax.ops.segment_min(idx, g.node_gid, g.num_graphs,
                                indices_are_sorted=True)
    return ids, idx - jnp.take(first, g.node_gid)


def count_routing(stack: nn.Module, stats, train, **more):
    """Routing counters of this step, summed over the expert layers (the
    imbalance averaged), kept in ``stack``'s ``batch_stats`` so that the
    train step's metrics can carry them out (trainer.model_counters);
    ``more``: further scalars of the stack's own, stored as ``moe_<key>``."""
    names = ("slots_held", "slots_all", "dense_steps", "load_max_over_mean",
             *more)
    cells = [stack.variable("batch_stats", f"moe_{k}",
                            lambda: jnp.zeros((), jnp.float32))
             for k in names]
    if not stats or not train or stack.is_initializing():
        return
    total = {k: sum(s[k] for s in stats)
             for k in ("slots_held", "slots_all", "dense_steps")}
    values = (total["slots_held"], total["slots_all"],
              total["dense_steps"],
              sum(s["load_max_over_mean"] for s in stats) / len(stats),
              *more.values())
    for cell, v in zip(cells, values):
        cell.value = v


def count_blocks(stack: nn.Module, blocks, train):
    """The attention kernels' block schedule of this step and the MB the
    attention halves' checkpoints keep, summed over the attending layers'
    forward calls (``blocks``: one ops/attention.py ``scheduled_blocks``
    and ``kept_mb`` each), kept as ``count_routing`` keeps its counters.
    ``attn_kept_mb`` is a number of the step's shape, the same every step."""
    cells = [stack.variable("batch_stats", f"attn_{k}",
                            lambda: jnp.zeros((), jnp.float32))
             for k in ("blocks_run", "blocks_band", "kept_mb")]
    if not train or stack.is_initializing():
        return
    for cell, values in zip(cells, zip(*blocks)):
        cell.value = jnp.asarray(sum(values), jnp.float32)


def count_kept(stack: nn.Module, kept, train, *blocks):
    """The MB that the checkpoints of the halves named in ``blocks`` keep
    in this step beyond attention's (``kept``: one ``{block: MB}`` a
    layer), summed over the layers and kept as ``<block>_kept_mb``, as
    ``count_blocks`` keeps ``attn_kept_mb``: numbers of the step's shape."""
    cells = {b: stack.variable("batch_stats", f"{b}_kept_mb",
                               lambda: jnp.zeros((), jnp.float32))
             for b in blocks}
    if not train or stack.is_initializing():
        return
    for b, cell in cells.items():
        cell.value = jnp.asarray(sum(m.get(b, 0.0) for m in kept),
                                 jnp.float32)
