"""Config system: accepts the reference's JSON schema, finalizes explicitly.

The reference mutates its config dict at runtime based on the loaded data
(``update_config``, reference hydragnn/utils/config_utils.py:23-106).  Here the
same inference is an explicit, pure step: :func:`finalize` takes the raw JSON
dict plus dataset statistics and returns the completed dict — output dims from
head specs, ``input_dim`` from selected features, PNA degree histogram,
edge-dim and equivariance validation — with identical key layout so existing
HydraGNN JSON configs work verbatim (e.g. reference tests/inputs/ci.json).
"""

from __future__ import annotations

import copy
import json
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from hydragnn_tpu.graph.batch import HeadSpec
from hydragnn_tpu.utils import tracer

# Architecture keys defaulted to None when absent, matching
# reference hydragnn/utils/config_utils.py:59-80.
_OPTIONAL_ARCH_KEYS = [
    "radius",
    "num_gaussians",
    "num_filters",
    "envelope_exponent",
    "num_after_skip",
    "num_before_skip",
    "basis_emb_size",
    "int_emb_size",
    "out_emb_size",
    "num_radial",
    "num_spherical",
]

def _telemetry_defaults() -> Dict[str, Any]:
    """Telemetry section defaults (docs/TELEMETRY.md), derived from the ONE
    source of truth — the TelemetryConfig dataclass — so the saved
    config.json can never document settings the run doesn't use.  Per-step
    structured metrics are opt-in (enable=0 keeps the hot path sync-free
    and file-free); the TensorBoard epoch scalars are unconditional."""
    from hydragnn_tpu.telemetry import TelemetryConfig

    d = TelemetryConfig()
    return {
        "enable": int(d.enable),
        "sinks": ",".join(d.sinks),
        # "" = the run-dir default (logs/<run>/telemetry); written back
        # so every key TelemetryConfig.from_section reads has a
        # documented default in the saved config.json (graftlint REG005)
        "dir": d.dir or "",
        "heartbeat": d.heartbeat,
        "ring": d.ring,
        "mfu": int(d.mfu),
        "trace": int(d.trace),
        "trace_ring": d.trace_ring,
    }


EDGE_MODELS = ["PNA", "CGCNN", "SchNet", "EGNN"]
# language models over each graph's nodes: the edge set is implicit, so no
# edge list is built (data/transform.py) and the longest graph bands the
# attention kernel (finalize).  THE table of the sequence stacks:
# ``model_type`` -> the stack's section of ``Architecture``, which is also
# its module under hydragnn_tpu/models/ (``Config``, ``Stack``;
# models/sequence.py says what a new one brings)
SEQUENCE_MODELS = {"Laguna": "laguna", "GlmMoeLite": "glm_moe_lite",
                   "NemotronH": "nemotron_h", "Lfm2Moe": "lfm2_moe",
                   "Qwen3Next": "qwen3_next"}
EQUIVARIANT_MODELS = ["EGNN", "SchNet"]
ALL_MODEL_TYPES = [
    "SAGE",
    "GIN",
    "GAT",
    "MFC",
    "PNA",
    "CGCNN",
    "SchNet",
    "DimeNet",
    "EGNN",
    *SEQUENCE_MODELS,
]


def load_config(path_or_dict) -> Dict[str, Any]:
    if isinstance(path_or_dict, dict):
        return copy.deepcopy(path_or_dict)
    with open(path_or_dict, "r") as f:
        return json.load(f)


def finalize(
    config: Dict[str, Any],
    dataset_stats: "DatasetStats",
) -> Dict[str, Any]:
    """Complete a raw config from dataset statistics (pure; returns a copy).

    Parity with reference update_config (hydragnn/utils/config_utils.py:23-106):
      - output_dim / output_type from Variables_of_interest + feature dims
      - input_dim = number of selected input node features
      - PNA degree histogram + max_neighbours
      - edge_dim validation (PNA/CGCNN/SchNet/EGNN only; CGCNN default 0)
      - equivariance validation (EGNN/SchNet only)
      - defaults: optimizer AdamW, loss mse, activation relu, SyncBatchNorm off
    """
    config = copy.deepcopy(config)
    nn = config["NeuralNetwork"]
    arch = nn["Architecture"]
    var = nn["Variables_of_interest"]
    training = nn["Training"]

    output_type: List[str] = var["type"]
    output_index: List[int] = var["output_index"]

    # Per-head output dims from the Dataset feature dims (reference
    # update_config_NN_outputs, config_utils.py:153-189).
    if "Dataset" in config and "node_features" in config["Dataset"]:
        gdims = config["Dataset"].get("graph_features", {}).get("dim", [])
        ndims = config["Dataset"]["node_features"]["dim"]
        dims_list = [
            gdims[output_index[i]] if t == "graph" else ndims[output_index[i]]
            for i, t in enumerate(output_type)
        ]
    else:
        dims_list = var["output_dim"]

    arch["output_dim"] = dims_list
    arch["output_type"] = output_type
    arch["num_nodes"] = int(dataset_stats.num_nodes_sample)

    if dataset_stats.graph_size_variable and (
        "node" in arch.get("output_heads", {})
        and arch["output_heads"]["node"].get("type") == "mlp_per_node"
        and "node" in output_type
    ):
        raise ValueError('"mlp_per_node" is not allowed for variable graph size')

    arch["input_dim"] = len(var["input_node_features"])
    if arch["model_type"] in SEQUENCE_MODELS:
        # no graph is longer than this: it bands the full-attention
        # layers' kernel (ops/attention.py)
        arch["max_graph_nodes"] = int(dataset_stats.max_nodes)

    if arch["model_type"] == "PNA":
        deg = dataset_stats.pna_deg
        assert deg is not None, "PNA requires a degree histogram in dataset stats"
        arch["pna_deg"] = [int(d) for d in deg]
        arch["max_neighbours"] = len(deg) - 1
    else:
        arch["pna_deg"] = None

    for key in _OPTIONAL_ARCH_KEYS:
        arch.setdefault(key, None)

    # edge_dim (reference update_config_edge_dim, config_utils.py:120-132)
    arch["edge_dim"] = None
    if arch.get("edge_features"):
        assert arch["model_type"] in EDGE_MODELS, (
            "Edge features can only be used with EGNN, SchNet, PNA and CGCNN."
        )
        arch["edge_dim"] = len(arch["edge_features"])
    elif arch["model_type"] == "CGCNN":
        arch["edge_dim"] = 0

    # equivariance (reference update_config_equivariance, config_utils.py:109-117)
    if arch.get("equivariance"):
        assert arch["model_type"] in EQUIVARIANT_MODELS, (
            "E(3) equivariance can only be ensured for EGNN and SchNet."
        )
    else:
        arch["equivariance"] = False

    arch.setdefault("freeze_conv_layers", False)
    arch.setdefault("initial_bias", None)
    training.setdefault("Optimizer", {"type": "AdamW", "learning_rate": 1e-3})
    training.setdefault("loss_function_type", "mse")
    arch.setdefault("activation_function", "relu")
    arch.setdefault("SyncBatchNorm", False)
    arch.setdefault("task_weights", [1.0] * len(output_type))
    var.setdefault("denormalize_output", False)
    # top-level Telemetry section (sibling of Profile): defaults written
    # back so the saved config.json documents the run's observability
    # settings; env knobs overlay at MetricsLogger construction
    # (telemetry/logger.py:TelemetryConfig.from_section)
    config.setdefault("Telemetry", {})
    for k, v in _telemetry_defaults().items():
        config["Telemetry"].setdefault(k, v)
    # top-level Serving section (docs/SERVING.md): same contract — the
    # saved config.json is what `python -m hydragnn_tpu.serve` later
    # loads, so write the knob defaults back AND the dataset-derived
    # per-graph worst case (the one piece of bucket sizing the serve-time
    # process cannot know without the training data); env knobs overlay
    # at ServingConfig.from_section.  Validation happens in the
    # ServingConfig dataclass on every construction path.
    from hydragnn_tpu.serve.config import serving_defaults

    config.setdefault("Serving", {})
    for k, v in serving_defaults().items():
        config["Serving"].setdefault(k, v)
    # unconditional, like edge_length_norm: the per-graph worst case is
    # THIS run's dataset provenance — a value inherited from a reused
    # config.json would size the serving buckets for the OLD dataset
    # and 413-reject valid graphs (serve-time overrides go through
    # HYDRAGNN_SERVE_MAX_NODES/_EDGES or editing the saved config)
    if dataset_stats.max_nodes:
        config["Serving"]["max_nodes_per_graph"] = int(
            dataset_stats.max_nodes)
    if dataset_stats.max_edges:
        config["Serving"]["max_edges_per_graph"] = int(
            dataset_stats.max_edges)
    # resilience knobs live in Training (they steer the trainer's step
    # builders and epoch driver); same defaults-written-back contract, env
    # knobs overlay at ResilienceConfig.from_training (docs/RESILIENCE.md)
    from hydragnn_tpu.resilience.config import resilience_training_defaults

    for k, v in resilience_training_defaults().items():
        training.setdefault(k, v)
    # elastic-resume policy (docs/RESILIENCE.md "Elastic training"):
    # default "strict" written back and VALIDATED on every construction
    # path — a typo'd policy must fail here, not silently refuse (or
    # silently admit) a resized resume.  The HYDRAGNN_ELASTIC_RESUME env
    # knob overlays at trainer build time (env wins).
    from hydragnn_tpu.resilience.elastic import check_elastic_policy

    training["elastic_resume"] = check_elastic_policy(
        training.get("elastic_resume", "strict"))
    # ZeRO sharding stage (docs/SCALING.md §4): default 0 (replicated DP)
    # written back like the other Training defaults, and VALIDATED on every
    # construction path — a typo'd stage must fail here, not silently train
    # replicated while the operator believes memory is sharded.  The
    # HYDRAGNN_ZERO env knob overlays at trainer build time (env wins).
    from hydragnn_tpu.parallel.zero import check_zero_stage

    training["zero_stage"] = check_zero_stage(training.get("zero_stage", 0))
    # training dtype policy (docs/PERF.md PR-15): default "f32" written
    # back like the other Training defaults, and VALIDATED on every
    # construction path — a typo'd policy must fail here, not silently
    # train f32 while the operator believes bf16 is on.  The
    # HYDRAGNN_TRAIN_DTYPE env knob overlays at trainer build time.
    from hydragnn_tpu.quant import check_train_policy

    training["train_dtype_policy"] = check_train_policy(
        training.get("train_dtype_policy", "f32"))
    # graph sharding backend/knobs (docs/SCALING.md §6): defaults written
    # back like the other Training defaults, and VALIDATED on every
    # construction path — a typo'd backend must fail here, not silently
    # train unsharded while the operator believes a giant graph fits.  The
    # HYDRAGNN_GRAPH_SHARD* env knobs overlay at trainer build time.
    from hydragnn_tpu.graph.partition import (
        check_graph_shard_backend,
        check_partition_method,
        graph_shard_training_defaults,
    )

    for k, v in graph_shard_training_defaults().items():
        training.setdefault(k, v)
    training["graph_shard"] = check_graph_shard_backend(
        training["graph_shard"])
    training["graph_shard_method"] = check_partition_method(
        training["graph_shard_method"])
    # streaming data-plane knobs (docs/DATA.md): Dataset-section defaults
    # written back like the other sections, and VALIDATED on every
    # construction path — a typo'd order mode must fail here, not silently
    # fall back to the in-memory loader.  The HYDRAGNN_STREAM* env knobs
    # overlay at data-loading time (env wins).
    from hydragnn_tpu.data.stream.config import (
        check_stream_flag,
        check_stream_order,
        stream_dataset_defaults,
    )

    config.setdefault("Dataset", {})
    dataset = config["Dataset"]
    for k, v in stream_dataset_defaults().items():
        dataset.setdefault(k, v)
    dataset["stream"] = check_stream_flag(dataset["stream"])
    dataset["stream_order"] = check_stream_order(dataset["stream_order"])
    return config


class DatasetStats:
    """Host-side dataset statistics needed to finalize a config."""

    def __init__(
        self,
        num_nodes_sample: int,
        graph_size_variable: bool,
        pna_deg: Optional[Sequence[int]] = None,
        max_nodes: Optional[int] = None,
        max_edges: Optional[int] = None,
        minmax_node_feature: Optional[np.ndarray] = None,
        minmax_graph_feature: Optional[np.ndarray] = None,
    ):
        self.num_nodes_sample = num_nodes_sample
        self.graph_size_variable = graph_size_variable
        self.pna_deg = pna_deg
        self.max_nodes = max_nodes or num_nodes_sample
        self.max_edges = max_edges
        self.minmax_node_feature = minmax_node_feature
        self.minmax_graph_feature = minmax_graph_feature

    @staticmethod
    @tracer.profile("setup.stats")
    def from_samples(samples, need_deg: bool = False) -> "DatasetStats":
        """Compute stats by scanning host-side GraphSamples (degree histogram
        parity with reference gather_deg, hydragnn/preprocess/utils.py:177-195)."""
        sizes = {s.num_nodes for s in samples}
        max_nodes = max(s.num_nodes for s in samples)
        max_edges = max(s.num_edges for s in samples)
        pna_deg = None
        if need_deg:
            max_deg = 0
            for s in samples:
                if s.num_edges:
                    d = np.bincount(s.edge_index[1], minlength=s.num_nodes)
                    max_deg = max(max_deg, int(d.max()))
            hist = np.zeros(max_deg + 1, dtype=np.int64)
            for s in samples:
                d = (
                    np.bincount(s.edge_index[1], minlength=s.num_nodes)
                    if s.num_edges
                    else np.zeros(s.num_nodes, dtype=np.int64)
                )
                hist += np.bincount(d, minlength=max_deg + 1)
            pna_deg = hist.tolist()
        return DatasetStats(
            num_nodes_sample=samples[0].num_nodes,
            graph_size_variable=len(sizes) > 1,
            pna_deg=pna_deg,
            max_nodes=max_nodes,
            max_edges=max_edges,
        )


def head_specs_from_config(config: Dict[str, Any]) -> List[HeadSpec]:
    """Static head layout from a finalized config."""
    nn = config["NeuralNetwork"]
    var = nn["Variables_of_interest"]
    arch = nn["Architecture"]
    names = var.get("output_names", [f"head{i}" for i in range(len(var["type"]))])
    return [
        HeadSpec(name=names[i], type=t, dim=int(arch["output_dim"][i]))
        for i, t in enumerate(var["type"])
    ]


def label_slices_from_config(config):
    """Per-head (start, end) column slices into the packed graph_y / node_y
    sample arrays, from Dataset feature dims + output_index (parity with
    reference update_predicted_values, hydragnn/preprocess/utils.py:237-279)."""
    nn = config["NeuralNetwork"]
    var = nn["Variables_of_interest"]
    ds = config.get("Dataset", {})
    gdims = ds.get("graph_features", {}).get("dim", [])
    ndims = ds.get("node_features", {}).get("dim", [])
    gslices, nslices = [], []
    for t, idx in zip(var["type"], var["output_index"]):
        if t == "graph":
            lo = int(sum(gdims[:idx]))
            gslices.append((lo, lo + int(gdims[idx])))
            nslices.append((0, 0))
        else:
            lo = int(sum(ndims[:idx]))
            nslices.append((lo, lo + int(ndims[idx])))
            gslices.append((0, 0))
    return gslices, nslices


def normalize_output_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """Fill ``Variables_of_interest.y_minmax`` from the serialized dataset's
    min/max headers so predictions can be denormalized (parity: reference
    normalize_output_config/update_config_minmax,
    hydragnn/utils/config_utils.py:192-240)."""
    var = config["NeuralNetwork"]["Variables_of_interest"]
    if not var.get("denormalize_output"):
        return config
    import pickle

    ds = config["Dataset"]
    base = os.environ.get("SERIALIZED_DATA_PATH", os.getcwd())
    label = "" if "total" in ds["path"] else "_train"
    fname = os.path.join(base, "serialized_dataset",
                         f"{ds['name']}{label}.pkl")
    with open(fname, "rb") as f:
        minmax_node = pickle.load(f)
        minmax_graph = pickle.load(f)
    y_minmax = []
    for t, idx in zip(var["type"], var["output_index"]):
        mm = minmax_graph if t == "graph" else minmax_node
        y_minmax.append([float(mm[0, idx]), float(mm[1, idx])])
    var["y_minmax"] = y_minmax
    return config


def get_log_name_config(config: Dict[str, Any]) -> str:
    """Run-name string, same fields as reference get_log_name_config
    (hydragnn/utils/config_utils.py:243-276)."""
    nn = config["NeuralNetwork"]
    arch, training = nn["Architecture"], nn["Training"]
    name = config["Dataset"]["name"]
    trimmed = name[: name.rfind("_") if name.rfind("_") > 0 else None]
    return (
        f"{arch['model_type']}-r-{arch.get('radius')}-ncl-{arch['num_conv_layers']}"
        f"-hd-{arch['hidden_dim']}-ne-{training['num_epoch']}"
        f"-lr-{training['Optimizer']['learning_rate']}-bs-{training['batch_size']}"
        f"-data-{trimmed}"
        f"-node_ft-{''.join(str(x) for x in nn['Variables_of_interest']['input_node_features'])}"
        f"-task_weights-{''.join(str(w) + '-' for w in arch['task_weights'])}"
    )


def save_config(config: Dict[str, Any], log_name: str, path: str = "./logs/") -> None:
    from hydragnn_tpu.resilience.ckpt_io import atomic_write_json

    os.makedirs(os.path.join(path, log_name), exist_ok=True)
    # atomic: the saved config.json is what `python -m hydragnn_tpu.serve`
    # later loads — a crash mid-write must not tear it
    atomic_write_json(os.path.join(path, log_name, "config.json"), config)
