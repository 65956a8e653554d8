"""The benchmark's own pieces, on the CPU: files found by name, the FLOP
count against XLA's, the trace reduction, the useful-step counter, and the
entry's refusal to run without a chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import (correct, families, flops, peaks, reference, run, tracing,
                   traffic)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = run.load_benchmark()


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_files_are_found_by_name(cell):
    """Every cell names a configuration, a traffic mix and limits that
    exist, and every per-layer metric it reports has a reader whose layer,
    unit and end-to-end metric agree with BENCHMARK.json."""
    c = run.find_cell(BENCH, cell)
    config = traffic.load_json("configs", c["config"])
    mix = traffic.load_json("workloads", c["traffic"])
    limits = traffic.load_json("limits", cell)
    assert config["name"] == c["config"] and mix["name"] == c["traffic"]
    assert set(limits["limits"]) == set(correct.NUMBERS)
    flops.check(config)
    assert run.program_config(config) is not None
    for kind in ("end_to_end", "per_layer"):
        assert run.cell_metrics(BENCH, cell, kind)
    names = [m["name"] for m in BENCH["end_to_end"]]
    for m in run.cell_metrics(BENCH, cell, "per_layer"):
        mod = run.load_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["moves"] in names


def test_every_metric_file_is_listed():
    listed = {m["name"] for m in BENCH["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
             if f.endswith(".py")}
    assert files == listed


@pytest.mark.parametrize("name", ["paper-cifar10-cnn", "paper-mnist-cnn"])
def test_forward_flops_match_xla(name):
    """The FLOP count of one sample's forward pass against XLA's cost
    analysis of the program's own CNN forward on the CPU (XLA also counts
    bias adds, ReLUs and pools, under 1 % here)."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    config = traffic.load_json("configs", name)
    cfg = run.program_config(config)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1,) + tuple(cfg.input_hw), jnp.float32)
    cost = jax.jit(lambda p, x: model_lib.cnn_forward(p, x, cfg)).lower(
        params, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    fam = families.load(config)
    ours = fam.forward_flops(config)
    assert ours <= cost["flops"] <= ours * 1.01
    assert fam.params(config) == sum(
        int(l.size) for l in jax.tree_util.tree_leaves(params))


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _naive_busy(ops, t0, t1, step):
    """Busy time by sampling the window on a grid: the reduction's
    interval union, counted another way."""
    ts = np.arange(t0, t1, step) + step / 2
    busy = np.zeros(ts.shape, bool)
    for a, b, *_ in ops:
        busy |= (ts >= a) & (ts < b)
    return busy.sum() * step


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    """A trace recorded here, on the CPU: a window span around jitted
    products and a benchmark span. Busy time by interval union against a
    grid count, idle gaps that tile the window with it, and the span that
    was open in a gap."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            sum(range(200_000))
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load(tracing.xplane_path(str(tmp_path)))
    assert tr.ops and {s[2] for s in tr.spans} >= {tracing.WINDOW_SPAN,
                                                   "bench.host_wait"}
    t0, t1 = tr.window()
    busy = tracing.busy_ns(tr)
    assert 0 < busy < t1 - t0
    step = (t1 - t0) / 20_000
    assert abs(busy - _naive_busy(tr.ops, t0, t1, step)) <= 2 * step * (
        len(tr.ops) + 1)
    gaps = tracing.idle_gaps(tr)
    assert abs(sum(b - a for a, b in gaps) + busy - (t1 - t0)) < 1e-6 * (
        t1 - t0)
    assert all(g1[1] - g1[0] >= g2[1] - g2[0]
               for g1, g2 in zip(gaps, gaps[1:]))
    assert "host_wait" in [name for name, _ in tracing.gaps_by_activity(tr)]
    assert tracing.top_ops(tr)[0][1] > 0


def test_trace_reduction_by_hand():
    tr = tracing.Trace(
        ops=[(10, 20, "a", "m", ""), (15, 30, "b", "m", ""),
             (50, 60, "a", "m", ""), (95, 120, "c", "m", "")],
        modules=[(10, 30, "jit_run(1)"), (50, 60, "jit_many(2)")],
        spans=[(0, 100, "bench.window"), (30, 52, "bench.receive_many"),
               (35, 40, "bench.sketch")],
        devices=1)
    assert tracing.busy_ns(tr) == 20 + 10 + 5
    assert tracing.idle_gaps(tr) == [(60, 95), (30, 50), (0, 10)]
    assert tracing.gaps_by_activity(tr) == [
        ["host loop", pytest.approx(35e-9)],
        ["receive_many", pytest.approx(20e-9)],
        ["host loop", pytest.approx(10e-9)]]
    assert tracing.module_ns(tr, ("jit_run",)) == (20, 1)
    assert tracing.op_ns(tr, ("a",)) == (20, 2)
    assert tracing.top_ops(tr)[0] == ["m:a", pytest.approx(20e-9)]


def test_useful_step_counter_by_hand():
    """The probe's per-wave count on a small engine: clients of 20, 70
    and 140 samples, one epoch at batch 32, run 1, 2 and 4 steps (20 at
    its own batch of 20, the others drop their last partial batch). A wave
    of clients 0 and 1 fills a 4-row bucket and runs its longest member's
    2 steps, the trip count the program records, not the engine's 4: 1 + 2
    real member-steps of 4 x 2 executed."""
    import jax
    from repro.common import tree as tu
    from repro.data.loader import ClientDataset, StackedClients
    from repro.data.synthetic import SyntheticClassification
    from repro.federated.cohort import CohortEngine
    from bench.probe import Probe

    config = dict(TINY)
    cfg = run.program_config(config, exact=False)
    params = reference.init_weights(config, 0)
    rng = np.random.default_rng(0)
    clients = [ClientDataset(SyntheticClassification(
        rng.standard_normal((n, 8, 8, 1)).astype(np.float32),
        rng.integers(0, 10, n), 10)) for n in (20, 70, 140)]
    spec = tu.FlatSpec(params)
    engine = CohortEngine(cfg, StackedClients.from_datasets(clients), spec,
                          params, local_epochs=1, batch_size=32)
    assert int(engine.num_steps) == 4
    probe = Probe({"prefix_updates": 1, "rate_hint": 1.0},
                  1.0, spans=True)
    probe.state = "window"
    w = jax.numpy.stack([spec.flatten(params)] * 2)
    probe._cohort(engine.cohort_update, engine, w, [0, 1], [0.01] * 2, [1, 2])
    wave = probe.waves[-1]
    assert (wave["members"], wave["rows"]) == (2, 4)
    assert (wave["useful_steps"], wave["executed_steps"]) == (1 + 2, 4 * 2)
    assert wave["samples"] == 1 * 20 + 2 * 32


def test_run_refuses_without_a_chip():
    """On the CPU the entry exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_world_keeps_its_sizes_across_seeds():
    """Every seed gets the same client sizes, client c holding the split's
    share c; the seed draws the classes and the samples."""
    config = traffic.load_json("configs", "paper-cifar10-cnn")
    mix = dict(traffic.load_json("workloads", "dir0.1"), samples=5000)
    a = traffic.make_world(config, mix, 1)
    b = traffic.make_world(config, mix, 2**33 + 5)
    assert np.array_equal(a.sizes, b.sizes)
    split = traffic.client_class_counts(4500, 10, mix["clients"],
                                        mix["alpha"], mix["partition_seed"])
    assert np.array_equal(a.sizes, split.sum(axis=1))
    assert not np.array_equal(a.y_train, b.y_train)
    assert a.x_train.shape == b.x_train.shape == (4500, 32, 32, 3)
    assert np.bincount(a.y_train, minlength=10).sum() == 4500


TINY = {"name": "tiny-cnn", "program_config": "paper-mnist-cnn",
        "family": "cnn", "input_hw": [8, 8, 1], "cnn_channels": [4, 8],
        "cnn_kernel": 5, "mlp_hidden": [16], "num_classes": 10,
        "params": 1610, "param_dtype": "float32"}
