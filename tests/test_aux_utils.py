"""Unit tests for aux subsystems: SMILES parsing, atomic descriptors,
visualizer, SLURM nodelist parsing, orbax checkpointing, profiler schedule,
timers (parity with reference tests/test_atomicdescriptors.py and the aux
subsystem inventory in SURVEY.md §5)."""

import os

import numpy as np
import pytest


def test_smiles_parser_basic():
    from hydragnn_tpu.utils.smiles_utils import generate_graphdata_from_smilestr

    # ethanol: 3 heavy atoms, 2 bonds -> 4 directed edges
    g = generate_graphdata_from_smilestr("CCO", 1.23)
    assert g.num_nodes == 3
    assert g.num_edges == 4
    assert g.graph_y[0] == pytest.approx(1.23)

    # benzene: aromatic ring, 6 atoms, 6 ring bonds -> 12 directed edges
    g = generate_graphdata_from_smilestr("c1ccccc1", 0.0)
    assert g.num_nodes == 6
    assert g.num_edges == 12
    # aromatic flag set on every atom
    assert (g.x[:, 10] == 1.0).all()

    # branches and double bonds: acetic acid CC(=O)O
    g = generate_graphdata_from_smilestr("CC(=O)O", 0.0)
    assert g.num_nodes == 4
    assert g.num_edges == 6


def test_atomicdescriptors():
    from hydragnn_tpu.utils.atomicdescriptors import (
        atomicdescriptors,
        group_period,
    )

    assert group_period(1) == (1, 1)
    assert group_period(6) == (14, 2)
    assert group_period(8) == (16, 2)
    assert group_period(26) == (8, 4)

    ad = atomicdescriptors(element_types=["C", "H", "O"])
    f = ad.get_atom_features(6)
    assert f.shape[0] == 6
    assert np.all(f >= 0) and np.all(f <= 1)

    ad1h = atomicdescriptors(element_types=["C", "H", "O"], one_hot=True)
    f = ad1h.get_atom_features(8)
    assert f.shape[0] == 9  # 3 one-hot + 6 properties


def test_visualizer(tmp_path):
    from hydragnn_tpu.postprocess.visualizer import Visualizer

    v = Visualizer("viztest", num_heads=2, logs_dir=str(tmp_path))
    rng = np.random.RandomState(0)
    t = [rng.rand(50, 1), rng.rand(50, 1)]
    p = [x + 0.05 * rng.randn(50, 1) for x in t]
    v.create_scatter_plots(t, p, ["a", "b"])
    v.create_error_histograms(t, p)
    v.plot_history({"train": [1.0, 0.5], "val": [1.1, 0.6], "test": [1.2, 0.7]})
    v.num_nodes_plot([4, 8, 8, 2])
    out = os.listdir(os.path.join(str(tmp_path), "viztest"))
    assert {"scatter.png", "error_pdf.png", "history.png",
            "num_nodes.png"} <= set(out)


def test_visualizer_global_analysis(tmp_path):
    """Cond-mean + error-PDF global analysis and per-component vector parity
    (reference visualizer.py:134-279, 467-613)."""
    from hydragnn_tpu.postprocess.visualizer import Visualizer

    v = Visualizer("viztest2", num_heads=2, head_dims=[1, 3],
                   logs_dir=str(tmp_path))
    rng = np.random.RandomState(1)
    t_scalar = rng.rand(80, 1)
    p_scalar = t_scalar + 0.1 * rng.randn(80, 1)
    t_vec = rng.rand(60, 3)
    p_vec = t_vec + 0.05 * rng.randn(60, 3)
    v.create_plot_global_analysis("energy", t_scalar, p_scalar)
    v.create_plot_global_analysis("forces", t_vec, p_vec)
    v.create_parity_plot_vector("forces", t_vec, p_vec, 3)
    out = os.listdir(os.path.join(str(tmp_path), "viztest2"))
    assert {"global_analysis_energy.png", "global_analysis_forces.png",
            "parity_vector_forces.png"} <= set(out)

    # cond-mean helper: binned error means track the injected error scale
    xs, em = Visualizer._err_condmean(t_scalar, p_scalar)
    assert xs.shape == em.shape and len(xs) > 5
    assert 0.02 < em.mean() < 0.3


def test_visualizer_per_node_and_scalar_panels(tmp_path):
    """Remaining reference plot types: scalar parity+error-PDF combo,
    per-node error PDFs, per-node vector parity, and the all-heads global
    driver (reference visualizer.py:281-466, 519-613, 722-733)."""
    from hydragnn_tpu.postprocess.visualizer import Visualizer

    v = Visualizer("viztest3", num_heads=2, logs_dir=str(tmp_path))
    rng = np.random.RandomState(2)
    t_scalar = rng.rand(80, 1)
    p_scalar = t_scalar + 0.1 * rng.randn(80, 1)
    # fixed-size graphs: [num_samples, num_nodes] node scalars and
    # [num_samples, num_nodes*3] node vectors
    t_node = rng.rand(40, 6)
    p_node = t_node + 0.05 * rng.randn(40, 6)
    t_nvec = rng.rand(40, 6 * 3)
    p_nvec = t_nvec + 0.05 * rng.randn(40, 6 * 3)

    v.create_parity_plot_and_error_histogram_scalar("e", t_scalar, p_scalar)
    v.create_error_histogram_per_node("q", t_node, p_node)
    v.create_error_histogram_per_node("e", t_scalar, p_scalar)  # skipped
    v.create_parity_plot_per_node_vector("f", t_nvec, p_nvec)
    v.create_plot_global([t_scalar, t_node], [p_scalar, p_node], ["e", "q"])

    out = set(os.listdir(os.path.join(str(tmp_path), "viztest3")))
    assert {"parity_errpdf_e.png", "errpdf_per_node_q.png",
            "parity_per_node_f.png", "global_analysis_e.png",
            "global_analysis_q.png"} <= out
    assert "errpdf_per_node_e.png" not in out


def test_slurm_nodelist_parsing():
    from hydragnn_tpu.utils.slurm import parse_slurm_nodelist

    assert parse_slurm_nodelist("frontier[00001-00003]") == [
        "frontier00001", "frontier00002", "frontier00003"]
    assert parse_slurm_nodelist("node1,node2") == ["node1", "node2"]
    assert parse_slurm_nodelist("n[1,5-6]") == ["n1", "n5", "n6"]


def test_orbax_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp

    from hydragnn_tpu.train.trainer import TrainState
    from hydragnn_tpu.utils.checkpoint import (
        latest_step,
        restore_checkpoint,
        save_checkpoint,
    )

    state = TrainState(
        step=jnp.asarray(7),
        params={"w": jnp.arange(4.0)},
        batch_stats={"bn": {"mean": jnp.ones(3)}},
        opt_state={"m": jnp.zeros(4)},
    )
    d = str(tmp_path / "ckpt")
    save_checkpoint(state, d)
    assert latest_step(d) == 7
    skeleton = TrainState(
        step=jnp.asarray(0),
        params={"w": jnp.zeros(4)},
        batch_stats={"bn": {"mean": jnp.zeros(3)}},
        opt_state={"m": jnp.ones(4)},
    )
    restored = restore_checkpoint(skeleton, d)
    assert int(restored.step) == 7
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.arange(4.0))


def test_profiler_schedule(tmp_path, monkeypatch):
    from hydragnn_tpu.utils import profile as prof

    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",)))
    p = prof.Profiler({"enable": 1, "wait": 2, "warmup": 1, "active": 2,
                       "trace_dir": str(tmp_path / "tr")})
    for _ in range(10):
        p.step()
    assert [c[0] for c in calls] == ["start", "stop"]


def test_timers():
    from hydragnn_tpu.utils.time_utils import Timer, get_timer, reset_timers

    reset_timers()
    with Timer("region_a"):
        pass
    t = get_timer("region_a")
    assert t.count == 1 and t.total >= 0.0


# -- utils/tracer: registration, nesting, threads (both built-in tracers) -----


class _Recording:
    """A tracer that writes down what it is told."""

    def __init__(self):
        self.events = []

    def start(self, name):
        self.events.append(("start", name))

    def stop(self, name):
        self.events.append(("stop", name))

    def reset(self):
        self.events.clear()


def test_tracer_register_unregister():
    from hydragnn_tpu.utils import tracer as tr

    rec = _Recording()
    tr.register("rec", rec)
    try:
        assert tr.has("rec") and tr.get("rec") is rec
        with tr.timer("train"):
            pass
        tr.unregister("rec")
        tr.start("validate")
        tr.stop("validate")
    finally:
        tr.unregister("rec")        # a second time: no error
    assert not tr.has("rec")
    assert rec.events == [("start", "train"), ("stop", "train")]


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: enter and exit must
    pair up per thread, innermost first."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        import threading

        _FakeAnnotation.log.append(
            ("enter", self.name, threading.get_ident()))

    def __exit__(self, *exc):
        import threading

        _FakeAnnotation.log.append(
            ("exit", self.name, threading.get_ident()))


@pytest.fixture
def tracer_kind(request, monkeypatch):
    """(tracer, closed): ``closed()`` lists the regions the tracer has
    closed, as (name, thread) in closing order."""
    import threading

    from hydragnn_tpu.utils import tracer as tr

    if request.param == "timer":
        t = tr.TimerTracer()
        order = []
        stop = t.stop

        def stop_and_note(name):
            before = t.counts.get(name, 0)
            stop(name)
            if t.counts.get(name, 0) > before:
                order.append((name, threading.get_ident()))

        t.stop = stop_and_note
        return t, lambda: list(order)
    _FakeAnnotation.log = []
    monkeypatch.setattr("jax.profiler.TraceAnnotation", _FakeAnnotation)
    t = tr.JaxProfilerTracer()
    return t, lambda: [(n, th) for what, n, th in _FakeAnnotation.log
                       if what == "exit"]


@pytest.mark.parametrize("tracer_kind", ["timer", "jax"], indirect=True)
def test_tracer_regions_nest(tracer_kind):
    import threading

    t, closed = tracer_kind
    me = threading.get_ident()
    t.start("outer")
    t.start("inner")
    t.start("inner")            # the same name, open twice
    t.stop("inner")
    t.stop("inner")
    t.stop("inner")             # a third stop closes nothing
    t.stop("outer")
    assert closed() == [("inner", me), ("inner", me), ("outer", me)]


@pytest.mark.parametrize("tracer_kind", ["timer", "jax"], indirect=True)
def test_tracer_regions_are_per_thread(tracer_kind):
    """A prefetch thread that opens and closes ``data.collate`` must not
    close the trainer thread's region of the same name."""
    import threading

    t, closed = tracer_kind
    me = threading.get_ident()
    t.start("data.collate")
    other = {}

    def worker():
        other["id"] = threading.get_ident()
        t.stop("data.collate")          # not this thread's: nothing
        t.start("data.collate")
        t.stop("data.collate")

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    assert closed() == [("data.collate", other["id"])]
    t.stop("data.collate")
    assert closed() == [("data.collate", other["id"]), ("data.collate", me)]


def test_profiler_schedule_counts_optimizer_steps(tmp_path, monkeypatch):
    """Under scan-K a dispatch is K steps: wait/warmup/active still mean
    steps, and the trace holds at least one whole dispatch."""
    from hydragnn_tpu.utils import profile as prof

    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append("start"))
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append("stop"))
    cfg = {"enable": 1, "wait": 40, "warmup": 24, "active": 3,
           "trace_dir": str(tmp_path / "tr")}
    p = prof.Profiler(cfg)
    seen = []
    for _ in range(5):                  # five dispatches of K=32
        p.step(32)
        seen.append(list(calls))
    # 64 steps are dispatched after the second call; the third dispatch
    # is traced whole
    assert seen == [[], ["start"], ["start", "stop"], ["start", "stop"],
                    ["start", "stop"]]
    calls.clear()
    p = prof.Profiler(cfg)
    for _ in range(70):                 # K=1: 64 wait+warmup, 3 active
        p.step()
    assert calls == ["start", "stop"] and p._step == 67
