"""What two or more of the sequence stacks share (config/config.py
``SEQUENCE_MODELS``: models/laguna.py, glm_moe_lite.py, nemotron_h.py,
lfm2_moe.py, qwen3_next.py): language models over each graph's nodes.  A document is a
graph, a token a node, the nodes of a graph contiguous (graph/batch.py
collate); node ``i``'s position is its index inside its graph, and the
node input is an integer id (``g.x[:, 0]``, exact in float32).

A stack is its own file beside this one: a ``Config`` (the sizes held
here, from its section of ``Architecture``) and a ``Stack`` (a
``SequenceStack``), a row of ``SEQUENCE_MODELS``, and rows of
analysis/registry.py for the scope names it introduces.  No stack imports
another: what a second stack needs of a first moves here, and a change
here is a change to every stack that reads it (tests/test_sequence_parity.py
pins their traced programs).

Precision, for all of them: parameters float32.  With ``compute_dtype:
bfloat16`` the matrix products take bfloat16 operands and accumulate in
float32 (``dot``); the residual stream, the norms, the rotary angles, the
router (HIGHEST), the softmaxes and the loss stay float32.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.ops.attention import (
    graph_attention,
    kept_mb,
    named_mb,
    scheduled_blocks,
)
from hydragnn_tpu.ops.moe import routed_experts
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.telemetry import counters
from hydragnn_tpu.utils.scope import phase

# the correction bias's step (DeepSeek-V3's bias update speed; not in the
# configs: ``ASSUMED`` in the references)
BIAS_UPDATE_SPEED = 1e-3


class SequenceStack(nn.Module):
    """The base of a sequence stack.  ``cfg.lm`` / ``cfg.share`` carry the
    model; the trainer's contract is the other stacks':
    ``model.apply(variables, batch, train=...)`` -> a tuple with one output
    per head (the logits [N, V held])."""

    cfg: Any
    attention_backend: Optional[str] = None
    moe_backend: Optional[str] = None
    interpret: bool = False

    # trainer._loss_and_metrics: no bf16 cast of params and ids for us; the
    # stack casts for itself, because the trainer's boundary cast would
    # round the router and the ids
    casts_at_boundary = False
    # trainer.create_train_state: shape the parameters under jit (the
    # forward is far too large to run op by op just for their shapes)
    jit_init = True
    # telemetry bind_step: no in-run MFU estimate (XLA's cost model does
    # not see inside the attention and grouped-product kernels)
    cost_model_sees_flops = False

    @property
    def compute_dtype(self):
        return (jnp.bfloat16 if self.cfg.compute_dtype == "bfloat16"
                else jnp.float32)


def dot(x, w, dtype, out=jnp.float32):
    """Operands in ``dtype``, float32 accumulation, result in ``out``."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=out)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def fan_in(n):
    """The initialiser of a matrix read along ``n`` inputs."""
    return nn.initializers.normal(stddev=n ** -0.5)


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


def attend(q, k, v, node_gid, node_mask, *, keep, window=None, max_span,
           backend, interpret):
    """(``graph_attention``'s result, what the step counts of the call: the
    blocks it schedules and the MB a checkpoint under the policy ``keep``
    holds of it).  The modules that call it stay apart (ROADMAP D18)."""
    o = graph_attention(q, k, v, node_gid, node_mask, window=window,
                        max_span=max_span, backend=backend,
                        interpret=interpret)
    blocks = (*scheduled_blocks(node_gid, node_mask, window=window,
                                max_span=max_span),
              kept_mb(q, k, v, keep, backend=backend))
    return o, blocks


def _gated_mlp(u, w1, w3, w2, dtype, names=None):
    """The hidden products leave the MXU rounded to ``dtype`` (float32
    accumulation inside): at width 12288 a float32 hidden is 1 GB.
    ``names``: what to bind the two under for a checkpoint's policy (the
    dense feed-forward's; the shared experts bind nothing)."""
    h1, h3 = dot(u, w1, dtype, dtype), dot(u, w3, dtype, dtype)
    if names:
        h1, h3 = checkpoint_name(h1, names[0]), checkpoint_name(h3, names[1])
    h = (jax.nn.silu(h1.astype(jnp.float32)) * h3).astype(dtype)
    return dot(h, w2, dtype)


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


class DenseFFN(nn.Module):
    """The dense half of a layer, recomputed in the backward pass in
    ``DENSE_CHUNKS`` node slices: beside 16 bytes a parameter of weights,
    gradients and AdamW moments there is room for one half-layer's
    activations, not for five layers'.  ``policy``: what each slice's
    checkpoint keeps (``KEEP_FFN``, or None: a slice is recomputed from its
    input alone).  Of ``lm`` it reads ``hidden_size``,
    ``intermediate_size`` and ``rms_norm_eps``."""

    lm: Any
    dtype: Any
    policy: Any = None

    @nn.compact
    def __call__(self, h):
        d, f = self.lm.hidden_size, self.lm.intermediate_size
        norm = self.param("norm", nn.initializers.ones, (d,))
        w1 = self.param("w1", fan_in(d), (d, f))
        w3 = self.param("w3", fan_in(d), (d, f))
        w2 = self.param("w2", fan_in(f), (f, d))
        def ffn(hs):
            u = rms_norm(hs, norm, self.lm.rms_norm_eps)
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
    """Routed experts (ops/moe.py) beside gated shared experts.  Of ``lm``
    it reads ``hidden_size``, ``moe_intermediate_size``,
    ``shared_expert_intermediate_size``, ``rms_norm_eps``,
    ``num_experts_per_tok``, ``norm_topk_prob``,
    ``moe_routed_scaling_factor`` and ``router_scoring``.
    ``zero_centred``: the norm's parameter is ``w`` of the scale ``1 + w``
    and starts at 0.  ``shared_gate``: the shared experts' result is
    multiplied by ``sigmoid(u w_sg)`` of a ``hidden_size -> 1`` product
    (both: models/qwen3_next.py)."""

    lm: Any
    share: LayerShare
    dtype: Any
    backend: Optional[str]
    interpret: bool
    zero_centred: bool = False
    shared_gate: bool = False

    @nn.compact
    def __call__(self, h, node_mask, bias=None):
        """``bias`` [E]: the router's correction bias, where the model has
        one (``balance``); the stats then carry ``counts_all``."""
        lm, share, d = self.lm, self.share, self.lm.hidden_size
        f, fs = lm.moe_intermediate_size, lm.shared_expert_intermediate_size
        e = share.experts_held
        norm = self.param("norm", nn.initializers.zeros if self.zero_centred
                          else nn.initializers.ones, (d,))
        router = self.param("router", fan_in(d),
                            (d, share.num_experts_total))
        w1 = self.param("experts_w1", fan_in(d), (e, d, f))
        w3 = self.param("experts_w3", fan_in(d), (e, d, f))
        w2 = self.param("experts_w2", fan_in(f), (e, f, d))
        s1 = self.param("shared_w1", fan_in(d), (d, fs))
        s3 = self.param("shared_w3", fan_in(d), (d, fs))
        s2 = self.param("shared_w2", fan_in(fs), (fs, d))
        u = rms_norm(h, 1.0 + norm if self.zero_centred else norm,
                     lm.rms_norm_eps)
        y, stats = routed_experts(
            u, router, w1, w3, w2, share, node_mask=node_mask,
            top_k=lm.num_experts_per_tok, norm_topk=lm.norm_topk_prob,
            scale=lm.moe_routed_scaling_factor, scoring=lm.router_scoring,
            bias=bias, compute_dtype=self.dtype, backend=self.backend,
            interpret=self.interpret)
        with phase("moe.shared"):
            out = _gated_mlp(u, s1, s3, s2, self.dtype)
            if self.shared_gate:
                out = out * jax.nn.sigmoid(dot(u, self.param(
                    "shared_gate", fan_in(d), (d, 1)), self.dtype))
            return y + out, stats


def balance(stack: nn.Module, biases, stats, train):
    """The bias's step after a train step, and the step's counters kept in
    ``stack``: ``count_routing``'s and, over ALL the experts, the fullest
    one's slots over the mean (what the bias acts on) and the largest
    ``|b|``.  ``biases`` / ``stats``: the expert layers' bias variables and
    routing stats by layer name.  After a TRAIN step ``b <- b +
    BIAS_UPDATE_SPEED x sign(mean(c) - c)``, ``c`` the step's slots on each
    of ALL the experts over real nodes: this rank's own count; in the
    deployment it is summed over the ranks, and no code stands in for
    them.  Eval steps read ``b`` and leave it alone."""
    with phase("moe.bias"):
        counts = [s["counts_all"] for s in stats.values()]
        if train and not stack.is_initializing():
            for name, s in stats.items():
                c = s["counts_all"]
                biases[name].value = (
                    biases[name].value
                    + BIAS_UPDATE_SPEED * jnp.sign(jnp.mean(c) - c))
        count_routing(
            stack, list(stats.values()), train,
            load_all_max_over_mean=sum(
                jnp.max(c) / jnp.maximum(jnp.mean(c), 1.0)
                for c in counts) / len(counts),
            bias_abs_max=jnp.max(jnp.stack(
                [jnp.max(jnp.abs(b.value)) for b in biases.values()])))


# What a stack counts in a step, kept in its ``batch_stats`` so that the
# train step's metrics can carry it out (telemetry/counters.py keep).

def count_routing(stack: nn.Module, stats, train, **more):
    """Routing counters of this step, summed over the expert layers (the
    imbalance averaged); ``more``: further scalars of the ``moe`` block."""
    counters.keep(
        stack, "moe", train and bool(stats),
        ("slots_held", "slots_all", "dense_steps", "load_max_over_mean",
         *more),
        lambda: (*(sum(s[k] for s in stats)
                   for k in ("slots_held", "slots_all", "dense_steps")),
                 sum(s["load_max_over_mean"] for s in stats) / len(stats),
                 *more.values()))


def count_blocks(stack: nn.Module, blocks, train):
    """The attention kernels' block schedule of this step and the MB the
    attention halves' checkpoints keep, summed over the attending layers'
    forward calls (``blocks``: one ``attend`` each)."""
    counters.keep(stack, "attention", train,
                  ("blocks_run", "blocks_band", "kept_mb"),
                  lambda: map(sum, zip(*blocks)))


def count_kept(stack: nn.Module, kept, train, *blocks):
    """The MB that the checkpoints of the halves named in ``blocks`` keep
    in this step beyond attention's (``kept``: one ``{block: MB}`` a
    layer), summed over the layers: numbers of the step's shape."""
    for b in blocks:
        counters.keep(stack, b, train, ("kept_mb",),
                      lambda: (sum(m.get(b, 0.0) for m in kept),))
