"""LFM2-MoE (``lfm2_moe``): double-gated short convolutions over each
graph's nodes in three layers of four, qk-normed grouped-query attention in
the fourth, sigmoid routing under an expert bias with no shared expert, one
table for embedding and head: the thirteenth stack.

A document is a graph, a token a node (models/sequence.py, whose dense
feed-forward, correction bias, precision rules and counters this stack
reads; each half-layer is recomputed in the backward pass).  What is its
own:

* **A layer's first half is read from ``layer_types``** (``conv`` or
  ``full_attention``), its second from the layer's index (``<
  num_dense_layers``: dense, else experts).
* **``conv``**: ``[B | C | X] = u W_in``; ``y = C * conv(B * X)`` along each
  graph's nodes, ``conv_L_cache`` taps, no bias, no activation function
  (ops/sconv.py ``graph_short_conv``: a tap never reads another graph's
  node, a padding node, or before the axis); ``y W_out``.  With bfloat16
  products ``B``, ``C``, ``X`` leave the first product in bfloat16 (float32
  accumulation), the gates and the taps are float32 inside one fused pass,
  and ``y`` is written in bfloat16, the dtype the second product reads; the
  convolution's weight is float32.
* **``full_attention``**: grouped-query attention with a learned RMS norm
  over each query and key head before the rotation, full rotary, no gate
  (``graph_attention``, ops/attention.py).
* **Experts**: ``routed_experts`` alone (ops/moe.py; sigmoid scores, the
  bias that models/sequence.py ``balance`` steps, the weights renormalised
  over ``sum + 1e-6``).  There is NO shared expert: a node none of whose
  selected experts is held here gets nothing from this half but the
  residual.
* **One table**: ``embed`` is read by ``lm.embed`` and, transposed, by
  ``lm.head``; its gradient is the sum of both uses.

The equations are LiquidAI/LFM2-24B-A2B's ``config.json`` as
models/lfm2_moe_reference.py writes them down; that file is the independent
float32 reference the tests and the benchmark hold this stack to, and it
names the forms the config is silent on (``ASSUMED``).  docs/LFM2_MOE.md has
the share and what is not there.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.lfm2_moe_reference import apply_rotary
from hydragnn_tpu.models.sequence import (
    KEEP_FFN,
    DenseFFN,
    SequenceStack,
    attend,
    balance,
    count_blocks,
    count_kept,
    dot,
    fan_in,
    ids_and_positions,
    rms_norm,
    where_narrow,
)
from hydragnn_tpu.ops.attention import KEEP_ATTN, named_mb
from hydragnn_tpu.ops.moe import KEEP_ROUTE, routed_experts
from hydragnn_tpu.ops.sconv import conv_counts, graph_short_conv
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.telemetry import counters
from hydragnn_tpu.utils.scope import phase

ROUTE_NORM_EPS = 1e-6       # the family's block; ops/moe.py route

# What the checkpoint round a short-convolution half keeps: the input
# product's result ``[B | C | X]``, [N, 3 x hidden] in the compute dtype
# (289 MB a layer at 23,512 nodes in bfloat16; the last axis is 48 x 128
# lanes, so what the chip holds is what the shape says).  With it kept the
# recomputed forward runs the norm (the weight's gradient reads it), the
# three slices and ``sconv.core`` (the output product's gradient reads
# ``y``), and NOT the 2048 -> 6144 product: 5.9e11 FLOP a layer.  ``y`` is
# not kept: rebuilding it is one fused memory-bound pass, ~3 ms a step over
# four layers, against 0.39 GB.
SCONV_PROJ = "sconv.in.proj"
KEEP_SCONV = jax.checkpoint_policies.save_only_these_names(SCONV_PROJ)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The sizes held HERE, hashable (``Architecture.lfm2_moe``)."""

    hidden_size: int
    vocab_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    conv_L_cache: int
    norm_eps: float
    norm_topk_prob: bool
    routed_scaling_factor: float
    num_dense_layers: int
    layer_types: Tuple[str, ...]
    rope_theta: float
    max_graph_nodes: Optional[int] = None
    router_scoring: ClassVar[str] = "sigmoid"     # ops/moe.py route
    experts_key: ClassVar[str] = "num_experts"    # parallel/share.py

    @staticmethod
    def from_arch(arch: Dict[str, Any]) -> "Lfm2MoeConfig":
        lm = arch["lfm2_moe"]
        rope = lm["rope_parameters"]
        # forms of the family this stack does not compute
        for key, want in (("conv_bias", False), ("use_expert_bias", True),
                          ("tie_word_embeddings", True)):
            if lm.get(key, want) != want:
                raise ValueError(
                    f"Lfm2Moe: {key}={lm[key]!r} is not implemented")
        if rope.get("rope_type", "default") != "default":
            raise ValueError(
                f"Lfm2Moe: rope_type={rope['rope_type']!r} is not "
                "implemented")
        n = int(lm["num_hidden_layers"])
        kinds = tuple(lm["layer_types"])
        if len(kinds) != n or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(
                f"Lfm2Moe: layer_types {kinds!r} must name each of the "
                f"{n} layers 'conv' or 'full_attention'")
        heads = int(lm["num_attention_heads"])
        return Lfm2MoeConfig(
            hidden_size=int(lm["hidden_size"]),
            vocab_size=int(lm["vocab_size"]),
            intermediate_size=int(lm["intermediate_size"]),
            moe_intermediate_size=int(lm["moe_intermediate_size"]),
            num_experts=int(lm["num_experts"]),
            num_experts_per_tok=int(lm["num_experts_per_tok"]),
            num_attention_heads=heads,
            num_key_value_heads=int(lm["num_key_value_heads"]),
            head_dim=int(lm.get("head_dim")
                         or int(lm["hidden_size"]) // heads),
            conv_L_cache=int(lm["conv_L_cache"]),
            norm_eps=float(lm["norm_eps"]),
            norm_topk_prob=bool(lm.get("norm_topk_prob", True)),
            routed_scaling_factor=float(lm.get("routed_scaling_factor", 1.0)),
            num_dense_layers=int(lm["num_dense_layers"]),
            layer_types=kinds, rope_theta=float(rope["rope_theta"]),
            max_graph_nodes=arch.get("max_graph_nodes"))

    # what models/sequence.py's dense feed-forward reads
    @property
    def rms_norm_eps(self) -> float:
        return self.norm_eps

    @property
    def expert_layers(self):
        """Names of the layers that hold a router (and so a bias)."""
        return tuple(f"layer_{i}" for i in range(self.num_dense_layers,
                                                 len(self.layer_types)))


class ShortConv(nn.Module):
    lm: Lfm2MoeConfig
    dtype: Any

    @nn.compact
    def __call__(self, x, node_gid, node_mask):
        lm, d, taps = self.lm, self.lm.hidden_size, self.lm.conv_L_cache
        norm = self.param("norm", nn.initializers.ones, (d,))
        w_in = self.param("w_in", fan_in(d), (d, 3 * d))
        conv_w = self.param(
            "conv_w", lambda k, s: jax.random.uniform(
                k, s, jnp.float32, -taps ** -0.5, taps ** -0.5), (taps, d))
        w_out = self.param("w_out", fan_in(d), (d, d))
        with phase("sconv.in"):
            proj = checkpoint_name(
                dot(rms_norm(x, norm, lm.norm_eps), w_in, self.dtype,
                    self.dtype), SCONV_PROJ)
        with phase("sconv.core"):
            y = graph_short_conv(proj[:, :d], proj[:, d:2 * d],
                                 proj[:, 2 * d:], conv_w, node_gid,
                                 node_mask)
        with phase("sconv.out"):
            return dot(y, w_out, self.dtype)


class Attention(nn.Module):
    lm: Lfm2MoeConfig
    dtype: Any
    backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions):
        lm, d, hd = self.lm, self.lm.hidden_size, self.lm.head_dim
        heads, kv, n = lm.num_attention_heads, lm.num_key_value_heads, x.shape[0]
        norm = self.param("norm", nn.initializers.ones, (d,))
        wq = self.param("wq", fan_in(d), (d, heads * hd))
        wk = self.param("wk", fan_in(d), (d, kv * hd))
        wv = self.param("wv", fan_in(d), (d, kv * hd))
        q_norm = self.param("q_norm", nn.initializers.ones, (hd,))
        k_norm = self.param("k_norm", nn.initializers.ones, (hd,))
        wo = self.param("wo", fan_in(heads * hd), (heads * hd, d))
        with phase("attn.proj"):
            u = rms_norm(x, norm, lm.norm_eps)
            # each head normed over its own channels, then rotated (the
            # reference's own function: float32 angles)
            q = apply_rotary(rms_norm(
                dot(u, wq, self.dtype).reshape(n, heads, hd), q_norm,
                lm.norm_eps), positions, lm.rope_theta).astype(self.dtype)
            k = apply_rotary(rms_norm(
                dot(u, wk, self.dtype).reshape(n, kv, hd), k_norm,
                lm.norm_eps), positions, lm.rope_theta).astype(self.dtype)
            v = dot(u, wv, self.dtype, self.dtype).reshape(n, kv, hd)
        o, blocks = attend(q, k, v, node_gid, node_mask, keep=KEEP_ATTN,
                           max_span=lm.max_graph_nodes,
                           backend=self.backend, interpret=self.interpret)
        with phase("attn.proj"):
            return dot(o.reshape(n, heads * hd), wo, self.dtype), blocks


class Experts(nn.Module):
    lm: Lfm2MoeConfig
    share: LayerShare
    dtype: Any
    backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, h, node_mask, bias):
        lm, share, d = self.lm, self.share, self.lm.hidden_size
        f, e = lm.moe_intermediate_size, share.experts_held
        norm = self.param("norm", nn.initializers.ones, (d,))
        router = self.param("router", fan_in(d), (d, share.num_experts_total))
        w1 = self.param("experts_w1", fan_in(d), (e, d, f))
        w3 = self.param("experts_w3", fan_in(d), (e, d, f))
        w2 = self.param("experts_w2", fan_in(f), (e, f, d))
        return routed_experts(
            rms_norm(h, norm, lm.norm_eps), router, w1, w3, w2, share,
            node_mask=node_mask, top_k=lm.num_experts_per_tok,
            norm_topk=lm.norm_topk_prob, scale=lm.routed_scaling_factor,
            scoring=lm.router_scoring, bias=bias, compute_dtype=self.dtype,
            backend=self.backend, interpret=self.interpret,
            norm_eps=ROUTE_NORM_EPS)


class Lfm2Layer(nn.Module):
    lm: Lfm2MoeConfig
    share: LayerShare
    layer: int
    dtype: Any
    attention_backend: Optional[str] = None
    moe_backend: Optional[str] = None
    interpret: bool = False

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions, bias):
        """(x after both halves, routing stats or None, attention's
        scheduled blocks or None, the MB the other halves' checkpoints
        keep: ``{"sconv": MB}`` on a conv layer, ``{"ffn": MB}`` on a dense
        one).  Each half is recomputed in the backward pass from its input
        and from what its checkpoint keeps by name: in bfloat16
        (models/sequence.py where_narrow) the short convolution its input
        product (KEEP_SCONV) and the dense feed-forward, slice by slice,
        its two up-products (models/sequence.py KEEP_FFN), 1.16 + 1.11 GB at
        23,512 nodes, where the step needs 13.2 of the device's 16.9 GB;
        the expert half its router's decision (ops/moe.py KEEP_ROUTE); the
        attention half the kernel's result and log-sum-exp and q, k, v, 8
        key/value heads beside 32 query heads, normed and rotated in
        float32 (ops/attention.py KEEP_ATTN)."""
        lm, blocks, stats, kept = self.lm, None, None, {}
        if lm.layer_types[self.layer] == "conv":
            keep = where_narrow(KEEP_SCONV, self.dtype)
            a = nn.remat(ShortConv, policy=keep)(
                lm, self.dtype, name="op")(x, node_gid, node_mask)
            kept["sconv"] = named_mb(keep, {
                SCONV_PROJ: jax.ShapeDtypeStruct(
                    (x.shape[0], 3 * lm.hidden_size), self.dtype)})
        else:
            a, blocks = nn.remat(Attention, policy=KEEP_ATTN)(
                lm, self.dtype, self.attention_backend, self.interpret,
                name="op")(x, node_gid, node_mask, positions)
        h = x + a
        if self.layer < lm.num_dense_layers:
            ffn = DenseFFN(lm, self.dtype,
                           where_narrow(KEEP_FFN, self.dtype), name="ffn")
            kept["ffn"] = ffn.kept_mb(h)
            return h + ffn(h), stats, blocks, kept
        y, stats = nn.remat(Experts, policy=KEEP_ROUTE)(
            lm, self.share, self.dtype, self.moe_backend, self.interpret,
            name="moe")(h, node_mask, bias)
        return h + y, stats, blocks, kept


class Lfm2MoeStack(SequenceStack):
    """One output: the logits [N, V held] for node ``i+1``'s id."""

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        lm, share, dtype = self.cfg.lm, self.cfg.share, self.compute_dtype
        # ONE table: the embedding's rows and, transposed, the head's
        # columns (as a head's matrix it is drawn by its fan-in)
        embed = self.param("embed", fan_in(lm.hidden_size),
                           (share.vocab_rows, lm.hidden_size))
        biases = {name: self.variable(
            "batch_stats", f"bias_{name}", lambda: jnp.zeros(
                (share.num_experts_total,), jnp.float32))
            for name in lm.expert_layers}
        with phase("lm.embed"):
            ids, positions = ids_and_positions(g, share)
            x = jnp.take(embed, ids, axis=0)
        stats, blocks, kept = {}, [], []
        for layer in range(len(lm.layer_types)):
            name = f"layer_{layer}"
            x, s, b, m = Lfm2Layer(
                lm, share, layer, dtype, self.attention_backend,
                self.moe_backend, self.interpret, name=name)(
                    x, g.node_gid, g.node_mask, positions,
                    biases[name].value if name in biases else None)
            if s is not None:
                stats[name] = s
            if b is not None:
                blocks.append(b)
            kept.append(m)
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (lm.hidden_size,))
        with phase("lm.head"):
            logits = dot(rms_norm(x, final_norm, lm.norm_eps), embed.T,
                         dtype)
        if biases:
            balance(self, biases, stats, train)
        if blocks:
            count_blocks(self, blocks, train)
        self._count_convs(g, train)
        count_kept(self, kept, train, "sconv", "ffn")
        return (logits,)

    def _count_convs(self, g, train):
        """What the short convolutions of this step met, summed over the
        ``conv`` layers (all of them meet the same rows): real rows, graph
        starts (the step's real graphs a layer), taps of real rows that
        read zero at a boundary.  Counted from the batch by ``tap_reach``,
        the function every layer's boundaries come from, not inside the
        layers: the block says what the batch put to them, not what each
        one did.  A method, because its name is the scope its operations
        are found under."""
        lm = self.cfg.lm
        layers = lm.layer_types.count("conv")
        counters.keep(
            self, "sconv", train and layers > 0,
            ("rows", "starts", "taps_cut"),
            lambda: (layers * v for v in conv_counts(
                g.node_gid, g.node_mask, lm.conv_L_cache)))


Config, Stack = Lfm2MoeConfig, Lfm2MoeStack
