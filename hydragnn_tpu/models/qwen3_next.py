"""Qwen3-Next (``qwen3_next``): Gated DeltaNet over each graph's nodes in
three layers of four (linear attention whose state is a matrix a head under
a gated delta rule), output-gated partial-rotary grouped-query attention in
the fourth, softmax top-k experts beside a sigmoid-gated shared expert in
EVERY layer: the fourteenth stack.

A document is a graph, a token a node (models/sequence.py, whose expert
module, precision rules and counters this stack reads; each half-layer is
recomputed in the backward pass).  What is its own:

* **Every norm over the hidden size and over a query or key head is
  zero-centred**: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``, ``w`` stored
  and initialised 0 (``Qwen3NextRMSNorm``).  The DeltaNet's gated norm is
  not.
* **A layer's first half is one of two mixers** by ``(l + 1) %
  full_attention_interval``; its second half is always experts.
* **Gated DeltaNet**: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``;
  ``silu`` of a 4-tap depthwise causal convolution of ``[q | k | v]`` along
  each graph's nodes (ops/ssm.py ``graph_causal_conv``, no bias); l2-normed
  ``q`` and ``k``, ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)`` and the rule itself (ops/gdn.py ``graph_gated_delta``: state
  and taps stop at every graph boundary); ``rms(o) * w_n * silu(z)`` a
  head; one output product.  With bfloat16 products ``q``, ``k``, ``v``
  enter the rule in bfloat16; ``g``, ``beta``, the state and both norms are
  float32.
* **Attention**: the query product is twice as wide and its second half,
  per head, gates the kernel's result elementwise (``o * sigmoid(gate)``);
  ``q`` and ``k`` are normed a head (zero-centred) and rotated over their
  first ``head_dim * partial_rotary_factor`` dims (``graph_attention``,
  ops/attention.py).
* **Experts**: models/sequence.py ``MoE`` (softmax over all the experts,
  the k largest, renormalised; ops/moe.py) with its zero-centred norm and
  the shared expert's ``sigmoid(u w_sg)`` gate switched on.

The equations are Qwen/Qwen3-Next-80B-A3B-Instruct's ``config.json`` as
models/qwen3_next_reference.py writes them down; that file is the
independent float32 reference the tests and the benchmark hold this stack
to, and it names the forms the config is silent on (``ASSUMED``) and where
a layout departs (``DEPARTURES``).  docs/QWEN3_NEXT.md has the share and
what is not there.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.qwen3_next_reference import apply_rotary, layer_kinds
from hydragnn_tpu.models.sequence import (
    MoE,
    SequenceStack,
    attend,
    count_blocks,
    count_kept,
    count_routing,
    dot,
    fan_in,
    ids_and_positions,
    rms_norm,
    where_narrow,
)
from hydragnn_tpu.ops.attention import KEEP_ATTN, named_mb
from hydragnn_tpu.ops.gdn import GDN_INV, graph_gated_delta, inverse_bytes
from hydragnn_tpu.ops.moe import KEEP_ROUTE
from hydragnn_tpu.ops.ssm import graph_causal_conv, scan_counts
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.telemetry import counters
from hydragnn_tpu.utils.scope import phase

L2_EPS = 1e-6           # under the root of q's and k's l2 norm
# the published code's draws of A and dt (ASSUMED in the reference)
A_MAX, DT_MIN, DT_MAX = 16.0, 1e-3, 1e-1

# What the checkpoint round a DeltaNet half keeps: the delta rule's inverse
# ``(I + A)^-1`` (ops/gdn.py GDN_INV: float32 [chunks, H_v, C, C] as
# computed, 106.4 MB a layer at 12,968 nodes, 203 chunks of 64 and 32 heads).
# With it kept the recomputed forward runs everything up to the rule's ``A``
# and NOT the inverse's ten HIGHEST [64, 64] products a chunk-head: its
# backward rule reads the kept array.  In bfloat16 only (models/sequence.py
# where_narrow), on the ``chunked`` backend only (the ``sequential`` one
# computes no inverse and names nothing).
#
# The wide input product ``[q | k | v | z]`` is NOT kept beside it, though
# alone it was worth 7.6 ms a step (PR 44 kept it: 319 MB a layer).  With
# both kept in the first DeltaNet layer and the product in the second, the
# schedule the TPU compiler finds for the scanned step needs 17.3 GB of the
# device's 16.9 (its own rematerialisation runs and does not help; the same
# with the inverse named lane-dense), where the inverse alone needs 14.5,
# the product alone 14.7 and nothing 15.5 (whole-step AOT compiles for a
# described v5e: PERF.md section 6, PR 46, has every mix of layers).  Of the
# two the inverse is worth more.
KEEP_GDN = jax.checkpoint_policies.save_only_these_names(GDN_INV)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The sizes held HERE, hashable (``Architecture.qwen3_next``)."""

    hidden_size: int
    vocab_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    rms_norm_eps: float
    norm_topk_prob: bool
    layer_types: Tuple[str, ...]
    linear_chunk_size: int = 64
    max_graph_nodes: Optional[int] = None
    router_scoring: ClassVar[str] = "softmax"     # ops/moe.py route
    experts_key: ClassVar[str] = "num_experts"    # parallel/share.py
    moe_routed_scaling_factor: ClassVar[float] = 1.0

    @staticmethod
    def from_arch(arch: Dict[str, Any]) -> "Qwen3NextConfig":
        lm = arch["qwen3_next"]
        # forms of the family this stack does not compute
        for key, want in (("mlp_only_layers", []), ("decoder_sparse_step", 1),
                          ("rope_scaling", None), ("hidden_act", "silu"),
                          ("tie_word_embeddings", False),
                          ("use_sliding_window", False),
                          ("attention_bias", False)):
            if lm.get(key, want) != want:
                raise ValueError(
                    f"Qwen3Next: {key}={lm[key]!r} is not implemented")
        kinds = tuple(layer_kinds(lm))
        if (len(kinds) != int(lm["num_hidden_layers"])
                or set(kinds) - {"linear_attention", "full_attention"}):
            raise ValueError(
                f"Qwen3Next: layer_types {kinds!r} must name each of the "
                f"{lm['num_hidden_layers']} layers 'linear_attention' or "
                "'full_attention'")
        if (int(lm["linear_key_head_dim"]) != int(lm["linear_value_head_dim"])
                or int(lm["linear_num_value_heads"])
                % int(lm["linear_num_key_heads"])):
            raise ValueError(
                "Qwen3Next: the DeltaNet's key and value heads must be of "
                "one size and the value heads whole groups of key heads")
        # every field but the two made above is a key of the section; the
        # ones with a default may be left out
        sizes = {f.name: f.type for f in dataclasses.fields(Qwen3NextConfig)
                 if f.name not in ("layer_types", "max_graph_nodes")
                 and (f.default is dataclasses.MISSING or f.name in lm)}
        return Qwen3NextConfig(
            **{k: {"int": int, "float": float, "bool": bool}[t](lm[k])
               for k, t in sizes.items()},
            layer_types=kinds, max_graph_nodes=arch.get("max_graph_nodes"))


def zrms(x, w, eps):
    """The zero-centred RMS norm: the parameter is ``w``, the scale ``1 +
    w``."""
    return rms_norm(x, 1.0 + w, eps)


def _l2(x):
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform draw in [DT_MIN, DT_MAX]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(DT_MIN),
                                    math.log(DT_MAX)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, A_MAX))


class GatedDeltaNet(nn.Module):
    lm: Qwen3NextConfig
    dtype: Any
    backend: Optional[str]

    @nn.compact
    def __call__(self, x, node_gid, node_mask):
        lm, d, n = self.lm, self.lm.hidden_size, x.shape[0]
        hk, hv = lm.linear_num_key_heads, lm.linear_num_value_heads
        dk, dv = lm.linear_key_head_dim, lm.linear_value_head_dim
        key, value, taps = hk * dk, hv * dv, lm.linear_conv_kernel_dim
        conv = 2 * key + value
        norm = self.param("norm", nn.initializers.zeros, (d,))
        w_qkvz = self.param("w_qkvz", fan_in(d), (d, conv + value))
        w_ba = self.param("w_ba", fan_in(d), (d, 2 * hv))
        conv_w = self.param(
            "conv_w", lambda k, s: jax.random.uniform(
                k, s, jnp.float32, -taps ** -0.5, taps ** -0.5),
            (taps, conv))
        a_log = self.param("A_log", _a_log_init, (hv,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (hv,))
        gate_norm = self.param("gate_norm", nn.initializers.ones, (dv,))
        w_out = self.param("w_out", fan_in(value), (value, d))
        with phase("gdn.in"):
            u = zrms(x, norm, lm.rms_norm_eps)
            proj = dot(u, w_qkvz, self.dtype, self.dtype)
            ba = dot(u, w_ba, self.dtype)
        with phase("gdn.conv"):
            c = jax.nn.silu(graph_causal_conv(
                proj[:, :conv], conv_w, None, node_gid, node_mask))
        with phase("gdn.scan"):
            q = (_l2(c[:, :key].reshape(n, hk, dk)) * dk ** -0.5
                 ).astype(self.dtype)
            k = _l2(c[:, key:2 * key].reshape(n, hk, dk)).astype(self.dtype)
            v = c[:, 2 * key:].reshape(n, hv, dv).astype(self.dtype)
            beta = jax.nn.sigmoid(ba[:, :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)
        o = graph_gated_delta(q, k, v, g, beta, node_gid, node_mask,
                              chunk=lm.linear_chunk_size,
                              backend=self.backend)
        with phase("gdn.norm"):
            z = proj[:, conv:].reshape(n, hv, dv).astype(jnp.float32)
            y = (o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                + lm.rms_norm_eps) * gate_norm * jax.nn.silu(z))
        with phase("gdn.out"):
            return dot(y.reshape(n, value), w_out, self.dtype)


class Attention(nn.Module):
    lm: Qwen3NextConfig
    dtype: Any
    backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions):
        lm, d, hd = self.lm, self.lm.hidden_size, self.lm.head_dim
        heads, kv, n = lm.num_attention_heads, lm.num_key_value_heads, x.shape[0]
        rot = int(hd * lm.partial_rotary_factor)
        norm = self.param("norm", nn.initializers.zeros, (d,))
        # per head the first ``hd`` columns are q, the next ``hd`` the gate
        wq = self.param("wq", fan_in(d), (d, heads * 2 * hd))
        wk = self.param("wk", fan_in(d), (d, kv * hd))
        wv = self.param("wv", fan_in(d), (d, kv * hd))
        q_norm = self.param("q_norm", nn.initializers.zeros, (hd,))
        k_norm = self.param("k_norm", nn.initializers.zeros, (hd,))
        wo = self.param("wo", fan_in(heads * hd), (heads * hd, d))
        with phase("attn.proj"):
            u = zrms(x, norm, lm.rms_norm_eps)
            both = dot(u, wq, self.dtype).reshape(n, heads, 2 * hd)
            gate = jax.nn.sigmoid(both[..., hd:])
            # each head normed over its own channels, then rotated over its
            # first ``rot`` (the reference's own function: float32 angles)
            q = apply_rotary(zrms(both[..., :hd], q_norm, lm.rms_norm_eps),
                             positions, lm.rope_theta, rot).astype(self.dtype)
            k = apply_rotary(zrms(
                dot(u, wk, self.dtype).reshape(n, kv, hd), k_norm,
                lm.rms_norm_eps), positions, lm.rope_theta, rot
            ).astype(self.dtype)
            v = dot(u, wv, self.dtype, self.dtype).reshape(n, kv, hd)
        o, blocks = attend(q, k, v, node_gid, node_mask, keep=KEEP_ATTN,
                           max_span=lm.max_graph_nodes,
                           backend=self.backend, interpret=self.interpret)
        with phase("attn.proj"):
            o = o.astype(jnp.float32) * gate
            return dot(o.reshape(n, heads * hd), wo, self.dtype), blocks


class Qwen3NextLayer(nn.Module):
    lm: Qwen3NextConfig
    share: LayerShare
    layer: int
    dtype: Any
    attention_backend: Optional[str] = None
    moe_backend: Optional[str] = None
    gdn_backend: Optional[str] = None
    interpret: bool = False

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions):
        """(x after both halves, routing stats, attention's scheduled
        blocks or None, ``{"gdn": MB}`` that a DeltaNet half's checkpoint
        keeps).  Each half is recomputed in the backward pass from its
        input and from what its checkpoint keeps by name: the expert half
        its router's decision (ops/moe.py KEEP_ROUTE); the attention half
        the kernel's result and log-sum-exp and q, k, v, 2 key/value heads
        beside 16 query heads (ops/attention.py KEEP_ATTN); a DeltaNet half
        the chunked rule's float32 inverse where the products are narrow
        (KEEP_GDN, models/sequence.py where_narrow), else nothing."""
        lm, blocks, kept = self.lm, None, {}
        if lm.layer_types[self.layer] == "linear_attention":
            keep = where_narrow(KEEP_GDN, self.dtype)
            a = nn.remat(GatedDeltaNet, policy=keep)(
                lm, self.dtype, self.gdn_backend, name="mixer")(
                    x, node_gid, node_mask)
            kept["gdn"] = named_mb(keep, {GDN_INV: inverse_bytes(
                x.shape[0], lm.linear_num_value_heads, lm.linear_chunk_size,
                self.gdn_backend)})
        else:
            a, blocks = nn.remat(Attention, policy=KEEP_ATTN)(
                lm, self.dtype, self.attention_backend, self.interpret,
                name="mixer")(x, node_gid, node_mask, positions)
        h = x + a
        y, stats = nn.remat(MoE, policy=KEEP_ROUTE)(
            lm, self.share, self.dtype, self.moe_backend, self.interpret,
            zero_centred=True, shared_gate=True, name="moe")(h, node_mask)
        return h + y, stats, blocks, kept


class Qwen3NextStack(SequenceStack):
    """One output: the logits [N, V held] for node ``i+1``'s id."""

    gdn_backend: Optional[str] = None

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        lm, share, dtype = self.cfg.lm, self.cfg.share, self.compute_dtype
        embed = self.param("embed", nn.initializers.normal(stddev=1.0),
                           (share.vocab_rows, lm.hidden_size))
        with phase("lm.embed"):
            ids, positions = ids_and_positions(g, share)
            x = jnp.take(embed, ids, axis=0)
        stats, blocks, kept = [], [], []
        for layer in range(len(lm.layer_types)):
            x, s, b, m = Qwen3NextLayer(
                lm, share, layer, dtype, self.attention_backend,
                self.moe_backend, self.gdn_backend, self.interpret,
                name=f"layer_{layer}")(x, g.node_gid, g.node_mask, positions)
            stats.append(s)
            kept.append(m)
            if b is not None:
                blocks.append(b)
        final_norm = self.param("final_norm", nn.initializers.zeros,
                                (lm.hidden_size,))
        head = self.param("head", fan_in(lm.hidden_size),
                          (lm.hidden_size, share.vocab_rows))
        with phase("lm.head"):
            logits = dot(zrms(x, final_norm, lm.rms_norm_eps), head, dtype)
        count_routing(self, stats, train)
        if blocks:
            count_blocks(self, blocks, train)
        self._count_rule(g, train)
        count_kept(self, kept, train, "gdn")
        return (logits,)

    def _count_rule(self, g, train):
        """What the gated delta rule walked this step, summed over the
        DeltaNet layers (all of them walk the same chunks): chunks, those
        with no real node, graph starts (the step's real graphs a layer).
        A method, because its name is the scope its operations are found
        under."""
        lm = self.cfg.lm
        layers = lm.layer_types.count("linear_attention")
        counters.keep(
            self, "gdn", train and layers > 0,
            ("chunks", "chunks_padding", "resets"),
            lambda: (layers * c for c in scan_counts(
                g.node_gid, g.node_mask, lm.linear_chunk_size)))


Config, Stack = Qwen3NextConfig, Qwen3NextStack
