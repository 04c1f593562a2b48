"""Laguna: a sparse language model as the tenth stack.

A document is a graph, a token a node (models/sequence.py, whose dense
feed-forward, expert module, precision rules and counters this stack
reads).  The node's id is looked up in the held slice of the embedding; the
"conv" is a pre-norm block of gated grouped-query attention over each
graph's nodes (ops/attention.py, the edge set implicit) and a dense or
mixture-of-experts feed-forward (ops/moe.py); the node head is ONE untied
matrix over the held vocabulary rows, and the loss is ``softmax_xent``
against node ``i+1``'s id (models/layers.py).

The equations are poolside/Laguna-S-2.1's ``config.json`` (``model_type``
laguna) as models/laguna_reference.py writes them down; that file is the
independent float32 reference the tests and the benchmark hold this stack
to, and it names the forms the config is silent on (``ASSUMED``).  The
chip's share of the layer (experts, heads, vocabulary rows held) comes as
a ``LayerShare`` (parallel/share.py).

Each half of a layer (attention; feed-forward) is recomputed in the
backward pass from its input and from the few arrays its checkpoint keeps by
name because running them again is dear (the attention kernel's result,
log-sum-exp and operands: ops/attention.py ``KEEP_ATTN``; the router's
decision: ops/moe.py ``KEEP_ROUTE``; the dense feed-forward's two hidden
products: models/sequence.py ``KEEP_FFN``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.laguna_reference import apply_rotary
from hydragnn_tpu.models.sequence import (
    KEEP_FFN,
    DenseFFN,
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
from hydragnn_tpu.ops.attention import KEEP_ATTN
from hydragnn_tpu.ops.moe import KEEP_ROUTE
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
    experts_key: ClassVar[str] = "num_experts"    # parallel/share.py

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
        wq = self.param("wq", fan_in(d), (d, self.heads * hd))
        wk = self.param("wk", fan_in(d), (d, self.kv * hd))
        wv = self.param("wv", fan_in(d), (d, self.kv * hd))
        wg = self.param("wg", fan_in(d), (d, self.heads))
        wo = self.param("wo", fan_in(self.heads * hd),
                        (self.heads * hd, d))
        rope = lm.rope_of(self.kind)
        with phase("attn.proj"):
            u = rms_norm(x, norm, lm.rms_norm_eps)
            # the rotation is the reference's own function (float32
            # angles; the inverse frequencies are constants of the config)
            q = apply_rotary(
                dot(u, wq, self.dtype).reshape(n, self.heads, hd),
                positions, rope, hd).astype(self.dtype)
            k = apply_rotary(
                dot(u, wk, self.dtype).reshape(n, self.kv, hd),
                positions, rope, hd).astype(self.dtype)
            v = dot(u, wv, self.dtype).reshape(
                n, self.kv, hd).astype(self.dtype)
            gate = jax.nn.sigmoid(dot(u, wg, self.dtype))
        window = (lm.sliding_window if self.kind == "sliding_attention"
                  else None)
        o, blocks = attend(q, k, v, node_gid, node_mask, keep=KEEP_ATTN,
                           window=window, max_span=lm.max_graph_nodes,
                           backend=self.backend, interpret=self.interpret)
        with phase("attn.proj"):
            o = o.astype(jnp.float32) * gate[:, :, None]
            return dot(o.reshape(n, self.heads * hd), wo,
                       self.dtype), blocks


class LagunaStack(SequenceStack):
    """One output: the logits [N, V held] for node ``i+1``'s id."""

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        lm, share, dtype = self.cfg.lm, self.cfg.share, self.compute_dtype
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
        head = self.param("head", fan_in(lm.hidden_size),
                          (lm.hidden_size, share.vocab_rows))
        with phase("lm.head"):
            logits = dot(rms_norm(x, final_norm, lm.rms_norm_eps), head,
                         dtype)
        count_routing(self, stats, train)
        count_blocks(self, blocks, train)
        count_kept(self, kept, train, "ffn")
        return (logits,)


Config, Stack = LagunaConfig, LagunaStack
