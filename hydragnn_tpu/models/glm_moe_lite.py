"""GLM-4.7-Flash (``glm4_moe_lite``): latent attention, sigmoid routing under
a correction bias, and multi-token prediction as a second head: the
eleventh stack.

A document is a graph, a token a node (models/sequence.py, whose dense
feed-forward, expert module, correction bias, precision rules and counters
this stack reads; each half-layer is recomputed in the backward pass).
What is its own:

* **Latent attention (MLA)**, unabsorbed as training computes it: queries
  through a normed rank-768 bottleneck; keys and values rebuilt from ONE
  normed rank-512 latent a token; a 64-wide rotary key shared by all 20
  heads beside a 192-wide unrotated part; values 256 wide.  The core is
  ``graph_attention`` with as many key/value heads as query heads
  (ops/attention.py).  The absorbed form is a serving concern and is not
  built.
* **The correction bias** ``b`` (``e_score_correction_bias``, one [E] per
  expert layer, zeros at the start): state in ``batch_stats`` that no
  gradient moves.  Selection reads ``score + b``, the weights the unbiased
  scores (ops/moe.py route); models/sequence.py ``balance`` steps it after
  a train step.
* **Multi-token prediction**: ``h'_i = [RMSNorm(Emb(t_{i+1})) |
  RMSNorm(h_i)] Weh``, one more expert layer with its own router and bias,
  its own final norm, the MAIN head's matrix: the stack's second output,
  held to node ``i+2``'s id by the trainer's weighted multi-head loss
  (``task_weights``; the label is a third ``node_y`` column, -1 where a
  node has no second successor in its graph).

The equations are zai-org/GLM-4.7-Flash's ``config.json`` as
models/glm_moe_lite_reference.py writes them down; that file is the
independent float32 reference the tests and the benchmark hold this stack
to, and it names the forms the config is silent on (``ASSUMED``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional

import flax.linen as nn
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.glm_moe_lite_reference import apply_rotary
from hydragnn_tpu.models.sequence import (
    DenseFFN,
    MoE,
    SequenceStack,
    attend,
    balance,
    count_blocks,
    dot,
    fan_in,
    ids_and_positions,
    rms_norm,
)
from hydragnn_tpu.ops.attention import KEEP_ATTN_OUT
from hydragnn_tpu.ops.moe import KEEP_ROUTE
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.utils.scope import phase


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    """The sizes held HERE, hashable (``Architecture.glm_moe_lite``)."""

    hidden_size: int
    vocab_size: int
    intermediate_size: int
    moe_intermediate_size: int
    n_shared_experts: int
    n_routed_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    norm_topk_prob: bool
    routed_scaling_factor: float
    num_hidden_layers: int
    first_k_dense_replace: int
    num_nextn_predict_layers: int
    max_graph_nodes: Optional[int] = None
    router_scoring: ClassVar[str] = "sigmoid"     # ops/moe.py route
    experts_key: ClassVar[str] = "n_routed_experts"     # parallel/share.py

    @staticmethod
    def from_arch(arch: Dict[str, Any]) -> "GlmMoeLiteConfig":
        lm = arch["glm_moe_lite"]
        # forms of the family this stack does not compute
        for key, want in (("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("rope_scaling", None),
                          ("attention_bias", False),
                          ("partial_rotary_factor", 1),
                          ("hidden_act", "silu"),
                          ("num_key_value_heads",
                           lm["num_attention_heads"])):
            if lm.get(key, want) != want:
                raise ValueError(
                    f"GlmMoeLite: {key}={lm[key]!r} is not implemented")
        if int(lm.get("num_nextn_predict_layers", 0)) > 1:
            raise ValueError("GlmMoeLite: one prediction depth more at most")
        sizes = {f.name: f.type for f in dataclasses.fields(GlmMoeLiteConfig)
                 if f.name != "max_graph_nodes"}
        return GlmMoeLiteConfig(
            **{k: {"int": int, "float": float, "bool": bool}[t](lm[k])
               for k, t in sizes.items()},
            max_graph_nodes=arch.get("max_graph_nodes"))

    # what models/sequence.py's expert module reads
    @property
    def shared_expert_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def moe_routed_scaling_factor(self) -> float:
        return self.routed_scaling_factor

    @property
    def expert_layers(self):
        """Names of the layers that hold a router (and so a bias)."""
        return tuple(f"layer_{i}" for i in range(self.first_k_dense_replace,
                                                 self.num_hidden_layers)
                     ) + (("mtp",) if self.num_nextn_predict_layers else ())


class LatentAttention(nn.Module):
    lm: GlmMoeLiteConfig
    dtype: Any
    backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions):
        lm, d, heads = self.lm, self.lm.hidden_size, self.lm.num_attention_heads
        nope, rope, dv = lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim
        rq, rkv = lm.q_lora_rank, lm.kv_lora_rank
        n, eps = x.shape[0], lm.rms_norm_eps
        norm = self.param("norm", nn.initializers.ones, (d,))
        wdq = self.param("wdq", fan_in(d), (d, rq))
        q_norm = self.param("q_norm", nn.initializers.ones, (rq,))
        wuq = self.param("wuq", fan_in(rq), (rq, heads * (nope + rope)))
        wdkv = self.param("wdkv", fan_in(d), (d, rkv + rope))
        kv_norm = self.param("kv_norm", nn.initializers.ones, (rkv,))
        wukv = self.param("wukv", fan_in(rkv), (rkv, heads * (nope + dv)))
        wo = self.param("wo", fan_in(heads * dv), (heads * dv, d))
        with phase("mla.down"):
            u = rms_norm(x, norm, eps)
            cq = rms_norm(dot(u, wdq, self.dtype), q_norm, eps)
            down = dot(u, wdkv, self.dtype)
            ckv = rms_norm(down[:, :rkv], kv_norm, eps)
        with phase("mla.up"):
            q = dot(cq, wuq, self.dtype).reshape(n, heads, nope + rope)
            kv = dot(ckv, wukv, self.dtype).reshape(n, heads, nope + dv)
            # the rotation is the reference's own function (float32 angles)
            q = jnp.concatenate(
                [q[..., :nope],
                 apply_rotary(q[..., nope:], positions, lm.rope_theta)],
                axis=-1).astype(self.dtype)
            # ONE rotary key a token, read by every head
            kr = apply_rotary(down[:, None, rkv:], positions, lm.rope_theta)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(kr, (n, heads, rope))],
                axis=-1).astype(self.dtype)
            v = kv[..., nope:].astype(self.dtype)
        with phase("mla.core"):
            o, blocks = attend(
                q, k, v, node_gid, node_mask, keep=KEEP_ATTN_OUT,
                max_span=lm.max_graph_nodes, backend=self.backend,
                interpret=self.interpret)
        with phase("mla.out"):
            return dot(o.reshape(n, heads * dv), wo, self.dtype), blocks


class GlmLayer(nn.Module):
    lm: GlmMoeLiteConfig
    share: LayerShare
    dense: bool
    dtype: Any
    attention_backend: Optional[str] = None
    moe_backend: Optional[str] = None
    interpret: bool = False

    @nn.compact
    def __call__(self, x, node_gid, node_mask, positions, bias):
        lm = self.lm
        # each half recomputed in the backward pass from its input; the
        # attention half also from the kernel's kept result and log-sum-exp
        # (ops/attention.py KEEP_ATTN_OUT: no forward kernel runs twice).
        # q, k, v are NOT kept: 20 heads of 256 each, 0.54 GB a layer at
        # 17,512 nodes, rebuilt by two products from a 768- and a 512-wide
        # latent and one shared rotary key
        a, blocks = nn.remat(LatentAttention, policy=KEEP_ATTN_OUT)(
            lm, self.dtype, self.attention_backend, self.interpret,
            name="attn")(x, node_gid, node_mask, positions)
        h = x + a
        if self.dense:
            # no policy: each slice is recomputed from its input alone.
            # The two up-products (models/sequence.py KEEP_FFN) would be 0.72
            # GB at 17,512 nodes for ~9 ms of a 753 ms step, and this
            # stack's step needs 15.4 of the device's 16.9 GB without them
            return h + DenseFFN(lm, self.dtype, name="ffn")(h), None, blocks
        y, stats = nn.remat(MoE, policy=KEEP_ROUTE)(
            lm, self.share, self.dtype, self.moe_backend, self.interpret,
            name="moe")(h, node_mask, bias)
        return h + y, stats, blocks


class NextNextToken(nn.Module):
    """The multi-token-prediction module: logits for node ``i+2``'s id."""

    lm: GlmMoeLiteConfig
    share: LayerShare
    dtype: Any
    attention_backend: Optional[str]
    moe_backend: Optional[str]
    interpret: bool

    @nn.compact
    def __call__(self, h, ids, embed, head, g, positions, bias):
        lm, d, eps = self.lm, self.lm.hidden_size, self.lm.rms_norm_eps
        enorm = self.param("enorm", nn.initializers.ones, (d,))
        hnorm = self.param("hnorm", nn.initializers.ones, (d,))
        eh_proj = self.param("eh_proj", fan_in(2 * d), (2 * d, d))
        final_norm = self.param("final_norm", nn.initializers.ones, (d,))
        with phase("mtp.proj"):
            # nodes of a graph are contiguous: node i's successor is node
            # i+1 where that is a real node of the same graph.  A node
            # without one (a graph's last) is given its own id: nothing
            # counts its output, no other node reads it (causal), and it
            # is routed nowhere
            has_next = jnp.concatenate([
                (g.node_gid[1:] == g.node_gid[:-1]) & (g.node_mask[1:] > 0),
                jnp.zeros((1,), bool)])
            after = jnp.where(has_next, jnp.roll(ids, -1), ids)
            x = dot(jnp.concatenate(
                [rms_norm(jnp.take(embed, after, axis=0), enorm, eps),
                 rms_norm(h, hnorm, eps)], axis=-1), eh_proj, self.dtype)
        with phase("mtp.layer"):
            x, stats, blocks = GlmLayer(
                lm, self.share, False, self.dtype, self.attention_backend,
                self.moe_backend, self.interpret, name="layer")(
                    x, g.node_gid, g.node_mask * has_next, positions, bias)
        with phase("mtp.head"):
            return dot(rms_norm(x, final_norm, eps), head,
                       self.dtype), stats, blocks


class GlmMoeLiteStack(SequenceStack):
    """One output per head: the logits [N, V held] for node ``i+1``'s id
    and, with the multi-token-prediction module, for node ``i+2``'s."""

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        lm, share, dtype = self.cfg.lm, self.cfg.share, self.compute_dtype
        backends = (self.attention_backend, self.moe_backend, self.interpret)
        embed = self.param("embed", nn.initializers.normal(stddev=1.0),
                           (share.vocab_rows, lm.hidden_size))
        biases = {name: self.variable(
            "batch_stats", f"bias_{name}", lambda: jnp.zeros(
                (share.num_experts_total,), jnp.float32))
            for name in lm.expert_layers}
        with phase("lm.embed"):
            ids, positions = ids_and_positions(g, share)
            x = jnp.take(embed, ids, axis=0)
        stats, blocks = {}, []
        for layer in range(lm.num_hidden_layers):
            name = f"layer_{layer}"
            x, s, b = GlmLayer(lm, share, layer < lm.first_k_dense_replace,
                               dtype, *backends, name=name)(
                x, g.node_gid, g.node_mask, positions,
                biases[name].value if name in biases else None)
            blocks.append(b)
            if s is not None:
                stats[name] = s
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (lm.hidden_size,))
        head = self.param("head", fan_in(lm.hidden_size),
                          (lm.hidden_size, share.vocab_rows))
        with phase("lm.head"):
            logits = dot(rms_norm(x, final_norm, lm.rms_norm_eps), head,
                         dtype)
        outputs = (logits,)
        if "mtp" in biases:
            logits2, stats["mtp"], b = NextNextToken(
                lm, share, dtype, *backends, name="mtp")(
                    x, ids, embed, head, g, positions, biases["mtp"].value)
            blocks.append(b)
            outputs = (logits, logits2)
        if biases:
            balance(self, biases, stats, train)
        count_blocks(self, blocks, train)
        return outputs


Config, Stack = GlmMoeLiteConfig, GlmMoeLiteStack
