"""Nemotron-H (``nemotron_h``): Mamba-2 state-space layers over each graph's
nodes, experts in a latent space, one mixer or one feed-forward a layer:
the twelfth stack.

A document is a graph, a token a node (models/sequence.py, whose
correction bias, precision rules and counters this stack reads; the decays,
the running sums and the carried state are float32 too).  What is its own:

* **A layer is ONE mixer**: ``x <- x + Mixer(RMSNorm(x))``, the mixer read
  from the pattern string (``hybrid_override_pattern`` as held: ``M``
  Mamba-2, ``*`` attention, ``E`` LatentMoE).  There are no "two halves":
  a layer that stands alone is recomputed in the backward pass from its
  input, a scanned unit of the pattern (below) from the unit's input.
* **``M``**: one input product to ``[z | xBC | dt]``, the boundary-aware
  depthwise convolution and the selective scan of ops/ssm.py (state and
  taps stop at every graph boundary), the gated grouped norm, one output
  product.  The heads and groups held are the share's (whole groups, so
  the grouped norm is exact).
* **``*``**: grouped-query attention with NO positional term
  (``graph_attention``, ops/attention.py).
* **``E``**: the router reads the hidden state (sigmoid scores under a
  correction bias, models/sequence.py ``balance``), the experts live in a
  ``moe_latent_size``-wide space: ``routed_experts`` dispatches the latent
  rows to ungated ``relu^2`` experts (ops/moe.py ``rows=``, ``expert=``);
  the shared expert reads and writes the hidden space.
* **Runs of one repeated unit of the pattern are scanned** over stacked
  parameters (``EMEMEMEMEM*`` is five ``EM`` pairs and one ``*``): the
  traced and compiled program holds each kind of layer once, not once a
  layer.  ``layer_trees`` gives any tree of this stack's (parameters,
  gradients) back one entry a layer, as the reference names them.

The equations are nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's
``config.json`` as models/nemotron_h_reference.py writes them down; that
file is the independent float32 reference the tests and the benchmark hold
this stack to, and it names the forms the config is silent on
(``ASSUMED``).  docs/NEMOTRON_H.md has the share and what is not there.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.sequence import (
    SequenceStack,
    attend,
    balance,
    count_blocks,
    dot,
    fan_in,
    ids_and_positions,
    rms_norm,
)
from hydragnn_tpu.ops.attention import KEEP_ATTN
from hydragnn_tpu.ops.moe import KEEP_ROUTE, routed_experts
from hydragnn_tpu.ops.ssm import graph_causal_conv, graph_ssm, scan_counts
from hydragnn_tpu.parallel.share import LayerShare
from hydragnn_tpu.telemetry import counters
from hydragnn_tpu.utils.scope import phase


class Backends(NamedTuple):
    """Which implementation each operation takes (None: the platform's)."""
    attention: Optional[str] = None
    moe: Optional[str] = None
    ssm: Optional[str] = None
    interpret: bool = False


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The sizes held HERE, hashable (``Architecture.nemotron_h``)."""

    hidden_size: int
    vocab_size: int
    hybrid_override_pattern: str
    layer_norm_epsilon: float
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    max_graph_nodes: Optional[int] = None
    router_scoring: ClassVar[str] = "sigmoid"     # ops/moe.py route
    experts_key: ClassVar[str] = "n_routed_experts"     # parallel/share.py

    @staticmethod
    def from_arch(arch: Dict[str, Any]) -> "NemotronHConfig":
        lm = arch["nemotron_h"]
        # forms of the family this stack does not compute
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("attention_bias", False), ("mlp_bias", False),
                          ("mamba_proj_bias", False), ("use_bias", False),
                          ("use_conv_bias", True), ("n_shared_experts", 1),
                          ("mlp_hidden_act", "relu2"),
                          ("mamba_hidden_act", "silu"),
                          ("num_nextn_predict_layers", 0),
                          ("sliding_window", None),
                          ("tie_word_embeddings", False)):
            if lm.get(key, want) != want:
                raise ValueError(
                    f"NemotronH: {key}={lm[key]!r} is not implemented")
        pattern = str(lm["hybrid_override_pattern"])
        if not pattern or set(pattern) - set(LAYERS):
            raise ValueError(
                f"NemotronH: hybrid_override_pattern {pattern!r} must be "
                f"made of {tuple(LAYERS)}")
        if len(pattern) != int(lm.get("num_hidden_layers", len(pattern))):
            raise ValueError(
                f"NemotronH: the pattern {pattern!r} does not have "
                f"num_hidden_layers={lm['num_hidden_layers']} layers")
        sizes = {f.name: f.type for f in dataclasses.fields(NemotronHConfig)
                 if f.name != "max_graph_nodes"}
        return NemotronHConfig(
            **{k: {"int": int, "float": float, "bool": bool, "str": str}[t](
                lm[k]) for k, t in sizes.items()},
            max_graph_nodes=arch.get("max_graph_nodes"))


def segments(pattern: str) -> Tuple[Tuple[int, str, int], ...]:
    """The pattern as (first layer, unit, repeats): a unit of two layers or
    of one that repeats at least twice is one scanned segment (not one with
    an attention layer: its block counters are not carried out of a scan),
    every other layer a segment of its own."""
    out, i = [], 0
    while i < len(pattern):
        unit, reps = pattern[i], 1
        for width in (2, 1):
            cand, r = pattern[i:i + width], 1
            while len(cand) == width and pattern[
                    i + r * width:i + (r + 1) * width] == cand:
                r += 1
            if r >= 2 and "*" not in cand:
                unit, reps = cand, r
                break
        out.append((i, unit, reps))
        i += len(unit) * reps
    return tuple(out)


def segment_name(first: int, unit: str, reps: int) -> str:
    return (f"layer_{first}" if reps == 1
            else f"layers_{first}_{first + len(unit) * reps - 1}")


def layer_trees(tree, pattern: str):
    """``tree`` (this stack's parameters, or anything shaped like them)
    with one ``layer_<l>`` entry a layer: a scanned segment's stacked
    leaves are sliced.  What models/nemotron_h_reference.py takes."""
    out = {k: v for k, v in tree.items() if not k.startswith("layer")}
    for first, unit, reps in segments(pattern):
        seg = tree[segment_name(first, unit, reps)]
        if reps == 1:
            out[f"layer_{first}"] = seg
            continue
        for r in range(reps):
            for j in range(len(unit)):
                out[f"layer_{first + r * len(unit) + j}"] = jax.tree.map(
                    lambda a, r=r: a[r], seg[f"unit_{j}"])
    return out


def _dt_bias_init(lm):
    """The inverse softplus of a log-uniform draw in [time_step_min,
    time_step_max], floored at time_step_floor (Mamba-2's)."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(lm.time_step_min), math.log(lm.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, lo, hi)), lm.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


# Each of the three layers takes (x, node_gid, node_mask, bias) and returns
# (x + Mixer(RMSNorm(x)), routing stats or None, attention's scheduled
# blocks or None).

class Mamba2(nn.Module):
    lm: NemotronHConfig
    share: LayerShare
    dtype: Any
    backends: Backends

    @nn.compact
    def __call__(self, x, node_gid, node_mask, bias=None):
        lm, d = self.lm, self.lm.hidden_size
        heads, hd = lm.mamba_num_heads, lm.mamba_head_dim
        groups, state, taps = lm.n_groups, lm.ssm_state_size, lm.conv_kernel
        inner, conv = heads * hd, heads * hd + 2 * groups * state
        n = x.shape[0]
        norm = self.param("norm", nn.initializers.ones, (d,))
        in_proj = self.param("in_proj", fan_in(d), (d, inner + conv + heads))
        conv_w = self.param(
            "conv_w", lambda k, s: jax.random.uniform(
                k, s, jnp.float32, -taps ** -0.5, taps ** -0.5),
            (taps, conv))
        conv_b = self.param("conv_b", nn.initializers.zeros, (conv,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init(lm), (heads,))
        gate_norm = self.param("gate_norm", nn.initializers.ones, (inner,))
        out_proj = self.param("out_proj", fan_in(inner), (inner, d))
        with phase("ssm.in"):
            proj = dot(rms_norm(x, norm, lm.layer_norm_epsilon), in_proj,
                       self.dtype)
            z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + conv],
                          proj[:, inner + conv:])
        with phase("ssm.conv"):
            xbc = jax.nn.silu(graph_causal_conv(
                xbc, conv_w, conv_b, node_gid, node_mask)).astype(self.dtype)
            dt = jax.nn.softplus(dt + dt_bias)
        y = graph_ssm(
            xbc[:, :inner].reshape(n, heads, hd), dt, -jnp.exp(a_log),
            xbc[:, inner:inner + groups * state].reshape(n, groups, state),
            xbc[:, inner + groups * state:].reshape(n, groups, state),
            skip, node_gid, node_mask, chunk=lm.chunk_size,
            backend=self.backends.ssm)
        with phase("ssm.norm"):
            # gate first, then each group of channels by its own mean square
            g = (y.reshape(n, inner) * jax.nn.silu(z)).reshape(
                n, groups, inner // groups)
            g = g * jax.lax.rsqrt(
                jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                + lm.layer_norm_epsilon)
            y = g.reshape(n, inner) * gate_norm
        with phase("ssm.out"):
            return x + dot(y, out_proj, self.dtype), None, None


class Attention(nn.Module):
    lm: NemotronHConfig
    share: LayerShare
    dtype: Any
    backends: Backends

    @nn.compact
    def __call__(self, x, node_gid, node_mask, bias=None):
        lm, d, hd = self.lm, self.lm.hidden_size, self.lm.head_dim
        heads, kv, n = lm.num_attention_heads, lm.num_key_value_heads, x.shape[0]
        norm = self.param("norm", nn.initializers.ones, (d,))
        wq = self.param("wq", fan_in(d), (d, heads * hd))
        wk = self.param("wk", fan_in(d), (d, kv * hd))
        wv = self.param("wv", fan_in(d), (d, kv * hd))
        wo = self.param("wo", fan_in(heads * hd), (heads * hd, d))
        with phase("attn.proj"):
            u = rms_norm(x, norm, lm.layer_norm_epsilon)
            q, k, v = (dot(u, w, self.dtype, self.dtype).reshape(n, h, hd)
                       for w, h in ((wq, heads), (wk, kv), (wv, kv)))
        o, blocks = attend(q, k, v, node_gid, node_mask, keep=KEEP,
                           max_span=lm.max_graph_nodes,
                           backend=self.backends.attention,
                           interpret=self.backends.interpret)
        with phase("attn.proj"):
            return (x + dot(o.reshape(n, heads * hd), wo, self.dtype), None,
                    blocks)


class LatentMoE(nn.Module):
    lm: NemotronHConfig
    share: LayerShare
    dtype: Any
    backends: Backends

    @nn.compact
    def __call__(self, x, node_gid, node_mask, bias):
        lm, share, d = self.lm, self.share, self.lm.hidden_size
        f, fs = lm.moe_intermediate_size, lm.moe_shared_expert_intermediate_size
        e, lat = share.experts_held, lm.moe_latent_size
        norm = self.param("norm", nn.initializers.ones, (d,))
        router = self.param("router", fan_in(d), (d, share.num_experts_total))
        down = self.param("down", fan_in(d), (d, lat))
        w1 = self.param("experts_w1", fan_in(lat), (e, lat, f))
        w2 = self.param("experts_w2", fan_in(f), (e, f, lat))
        up = self.param("up", fan_in(lat), (lat, d))
        s1 = self.param("shared_w1", fan_in(d), (d, fs))
        s2 = self.param("shared_w2", fan_in(fs), (fs, d))
        u = rms_norm(x, norm, lm.layer_norm_epsilon)
        with phase("moe.latent"):
            latent = dot(u, down, self.dtype, self.dtype)
        routed, stats = routed_experts(
            u, router, w1, None, w2, share, node_mask=node_mask,
            top_k=lm.num_experts_per_tok, norm_topk=lm.norm_topk_prob,
            scale=lm.routed_scaling_factor, scoring=lm.router_scoring,
            bias=bias, compute_dtype=self.dtype, backend=self.backends.moe,
            interpret=self.backends.interpret, rows=latent, expert="relu2")
        with phase("moe.latent"):
            y = dot(routed, up, self.dtype)
        with phase("moe.shared"):
            # the hidden product leaves the MXU rounded to ``dtype`` (float32
            # accumulation inside), as models/sequence.py's feed-forward does
            h = jnp.square(jax.nn.relu(dot(u, s1, self.dtype, self.dtype)
                                       .astype(jnp.float32))
                           ).astype(self.dtype)
            return x + y + dot(h, s2, self.dtype), stats, None


LAYERS = {"M": Mamba2, "E": LatentMoE, "*": Attention}

# What every checkpoint of this stack keeps, the two name sets joined (a
# layer holds the names of its own kind only): an ``E`` layer's router
# decision, and a ``*`` layer's kernel result, log-sum-exp and q, k, v (one
# or two key/value heads beside 32 query heads: ops/attention.py KEEP_ATTN).
KEEP = jax.checkpoint_policies.save_from_both_policies(KEEP_ROUTE, KEEP_ATTN)


def _layer(kind, lm, share, dtype, backends, name, remat=True):
    """One layer; ``remat``: recomputed in the backward pass from its input
    and from what ``KEEP`` names in it."""
    cls = nn.remat(LAYERS[kind], policy=KEEP) if remat else LAYERS[kind]
    return cls(lm, share, dtype, backends, name=name)


class Unit(nn.Module):
    """The layers of one repeat of a scanned segment: the scan's body,
    recomputed in the backward pass from ITS input (``nn.remat(Unit)``
    below), so that the scan keeps one [N, hidden] array a repeat and not
    one a layer; inside that recomputation every layer but the last is
    checkpointed again, so what is alive at once is one layer's
    internals.  (A checkpoint a layer kept 16 KB a row a layer more: 1.5
    GB at the benchmark's cell, which did not fit; PERF.md section 6.)
    Both checkpoints keep what ``KEEP`` names: of ``ops/moe.py
    KEEP_ROUTE`` the router's logits and ids, 2.2 KB a row an ``E`` layer,
    so the router runs once a step and not three times."""

    lm: NemotronHConfig
    share: LayerShare
    unit: str
    dtype: Any
    backends: Backends

    @nn.compact
    def __call__(self, x, biases, node_gid, node_mask):
        """``biases`` [experts of the unit's ``E`` layers, E]."""
        stats, e = [], 0
        for j, kind in enumerate(self.unit):
            x, s, _ = _layer(
                kind, self.lm, self.share, self.dtype, self.backends,
                f"unit_{j}", remat=j < len(self.unit) - 1)(
                    x, node_gid, node_mask,
                    biases[e] if kind == "E" else None)
            if kind == "E":
                stats.append(s)
                e += 1
        return x, stats


class NemotronHStack(SequenceStack):
    """One output: the logits [N, V held] for node ``i+1``'s id."""

    ssm_backend: Optional[str] = None

    @nn.compact
    def __call__(self, g: GraphBatch, train: bool = True):
        lm, share, dtype = self.cfg.lm, self.cfg.share, self.compute_dtype
        pattern = lm.hybrid_override_pattern
        backends = Backends(self.attention_backend, self.moe_backend,
                            self.ssm_backend, self.interpret)
        embed = self.param("embed", nn.initializers.normal(stddev=1.0),
                           (share.vocab_rows, lm.hidden_size))
        biases = {f"layer_{i}": self.variable(
            "batch_stats", f"bias_layer_{i}", lambda: jnp.zeros(
                (share.num_experts_total,), jnp.float32))
            for i, kind in enumerate(pattern) if kind == "E"}
        with phase("lm.embed"):
            ids, _ = ids_and_positions(g, share)
            x = jnp.take(embed, ids, axis=0)
        stats, blocks = {}, []
        for first, unit, reps in segments(pattern):
            name = segment_name(first, unit, reps)
            if reps == 1:
                x, s, b = _layer(unit, lm, share, dtype, backends, name)(
                    x, g.node_gid, g.node_mask,
                    biases[name].value if unit == "E" else None)
                if s is not None:
                    stats[name] = s
                if b is not None:
                    blocks.append(b)
                continue
            layers = [first + r * len(unit) + j for r in range(reps)
                      for j in range(len(unit))]
            held = [i for i in layers if pattern[i] == "E"]
            per_unit = unit.count("E")
            stacked = jnp.stack(
                [biases[f"layer_{i}"].value for i in held]).reshape(
                    reps, per_unit, -1) if held else jnp.zeros((reps, 0, 1))
            x, scanned = nn.scan(
                nn.remat(Unit, policy=KEEP), variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(0, nn.broadcast, nn.broadcast), length=reps)(
                    lm, share, unit, dtype, backends, name=name)(
                        x, stacked, g.node_gid, g.node_mask)
            for k, i in enumerate(held):
                stats[f"layer_{i}"] = jax.tree.map(
                    lambda a, k=k: a[k // per_unit], scanned[k % per_unit])
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (lm.hidden_size,))
        head = self.param("head", fan_in(lm.hidden_size),
                          (lm.hidden_size, share.vocab_rows))
        with phase("lm.head"):
            logits = dot(rms_norm(x, final_norm, lm.layer_norm_epsilon),
                         head, dtype)
        if biases:
            balance(self, biases, stats, train)
        if blocks:
            count_blocks(self, blocks, train)
        if "M" in pattern:
            self._count_scan(g, train)
        return (logits,)

    def _count_scan(self, g, train):
        """What ONE state-space layer's scan walks this step (all of them
        walk the same chunks): chunks, those with no real node, and graph
        starts (the step's real graphs).  A method, because its name is the
        scope its operations are found under."""
        counters.keep(
            self, "ssm", train, ("chunks", "chunks_padding", "resets"),
            lambda: scan_counts(g.node_gid, g.node_mask,
                                self.cfg.lm.chunk_size))


Config, Stack = NemotronHConfig, NemotronHStack
