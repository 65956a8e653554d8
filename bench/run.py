"""One benchmark run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names its
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/workloads/<traffic>.json``); its output limits are
``bench/limits/<cell>.json``. The run holds one chip, in this one process:

1. JAX's persistent compilation cache goes where the program keeps it
   (``repro.launch.compile_cache``); the device must be a TPU with as many
   chips as the cell asks for, else the run exits non-zero and prints no
   result;
2. the configuration's family module (``bench.families``) makes the world
   from the seed and the mix, the weights on the device in one jitted
   call, and the program's client datasets over the world;
3. one FedPSA experiment runs through the program's normal entry,
   ``repro.federated.run_algorithm`` (``run_async``, the cohort engine, the
   scanned ingest and the compiled Pallas kernels), at the paper's
   86,400-unit horizon, twice: a warm pass that builds every program the
   measured pass will use, then the measured pass, whose first
   ``prefix_updates`` updates are recorded for the output check before the
   window measures the updates the mix ingests in about ``--seconds``
   (``rate_hint x --seconds``, the same waves in every run) and stops the
   experiment (``bench.probe``);
4. after the window, with the program's state freed, the reference replays
   the experiment's first ten aggregations (``bench.correct``).

With ``--trace 0`` the result line carries the end-to-end metrics, with
``--trace 1`` the per-layer ones (``bench/metrics/<name>.py``, read from
the profiler's trace of the window and from the probe's counters). The
numbers compared for ``correct`` are printed last on stderr and come last
in the result line, each beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Optional  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

ALGORITHM = "fedpsa"
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    pass


def load_benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's default device is {info['platform']}")
    if require_tpu and info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def load_metric(name: str):
    path = os.path.join(CHECKOUT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` (end_to_end / per_layer) this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def program_config(config: dict, exact: bool = True):
    """The program's configuration, checked against the benchmark's file
    on the keys its family module names (``FIELDS``); with ``exact`` off
    (the tests' small models), derived from it."""
    import dataclasses
    from bench import families
    from repro.configs import get_config
    cfg = get_config(config["program_config"])
    fields = families.load(config).FIELDS
    if not exact:
        return dataclasses.replace(cfg, **{
            k: tuple(config[k]) if isinstance(config[k], list) else config[k]
            for k in fields})
    for key in fields:
        have = getattr(cfg, key)
        want = config[key]
        if (list(have) if isinstance(have, tuple) else have) != want:
            raise ValueError(f"{config['name']}: the program's {key} is "
                             f"{have!r}, the configuration states {want!r}")
    return cfg


def sim_config(traffic: dict, shuffle_seed: int):
    from repro.federated import SimConfig
    lat = traffic["latency"]
    return SimConfig(
        num_clients=int(traffic["clients"]),
        concurrency=float(traffic["concurrency"]),
        local_epochs=int(traffic["local_epochs"]),
        batch_size=int(traffic["batch"]), lr=float(traffic["lr"]),
        lr_decay=float(traffic["lr_decay"]),
        horizon=float(traffic["horizon"]),
        eval_every=float(traffic["eval_every"]),
        latency_kind=lat["kind"], latency_lo=float(lat["lo"]),
        latency_hi=float(lat["hi"]), seed=shuffle_seed,
        timeline_seed=int(traffic["timeline_seed"]))


@dataclass
class Context:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) reads."""
    config: dict
    traffic: dict
    peaks: dict
    params: int             # the trained parameters the server holds
    t_start: float
    t_end: float
    window_s: float
    counters: dict
    spans: list
    compile_setup_s: float
    compiles_window: int
    trace: object


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             bench: dict, require_tpu: bool = True,
             config: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None,
             limits: Optional[dict] = None, fault: Optional[str] = None,
             keep: Optional[dict] = None) -> dict:
    """One run; returns the result line as a dict.

    The keywords past ``bench`` serve the benchmark's tests and its
    calibration (``bench/calibrate.py``), never its runs: a small model and
    world on the CPU (``config``, ``traffic_overrides``), other limits, a
    fault planted in the program (``bench.faults``), and ``keep``, a dict
    that receives the world, the weights, the record and the reference's
    outputs."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from bench import correct, faults, families, flops, peaks as peak_table
    from bench import probe as probe_lib, reference, traffic as tr

    if require_tpu:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = device_info(int(cell["chips"]), require_tpu)
    peaks = peak_table.peaks(device["kind"]) if require_tpu else None

    exact = config is None
    config = config or tr.load_json("configs", cell["config"])
    traffic = dict(tr.load_json("workloads", cell["traffic"]))
    traffic.update(traffic_overrides or {})
    limits = limits if limits is not None else tr.load_json(
        "limits", cell["name"])["limits"]
    flops.check(config)
    cfg = program_config(config, exact)

    from repro.core import PSAConfig
    from repro.federated import run_algorithm

    family = families.load(config)
    sub = tr.sub_seeds(seed)
    world = family.make_world(config, traffic, seed)
    w0 = family.init_weights(config, seed)
    clients, test = family.program_inputs(world)
    sim = sim_config(traffic, sub["shuffle"])
    t_world = time.perf_counter()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window_span = []

    def start():
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            window_span.append(jax.profiler.TraceAnnotation("bench.window"))
            window_span[0].__enter__()

    def stop():
        if trace_dir:
            window_span[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    clock = probe_lib.CompileClock()
    # a traced run measures no end-to-end metric; its window is kept short
    # so the trace stays small enough to read within a run's time
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    probe = probe_lib.Probe(traffic, window, spans=trace,
                            on_window_start=start, on_window_end=stop)
    try:
        planted = (faults.plant(fault, cfg.family) if fault is not None
                   else contextlib.nullcontext())
        with planted, probe:
            for done in (probe_lib.WarmPassDone, probe_lib.WindowClosed):
                try:
                    run_algorithm(ALGORITHM, cfg, w0, clients, test, sim,
                                  psa_cfg=PSAConfig(),
                                  calib_batch=world.calib)
                except done:
                    pass
                else:
                    raise RuntimeError(
                        f"the experiment reached its horizon after "
                        f"{probe.updates} updates, in its {probe.state} pass")
                if done is probe_lib.WarmPassDone:
                    probe.measure()
                    gc.collect()
        stats = [d.memory_stats() or {} for d in jax.devices()]
        peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        counters = probe.counters()
        dropped = (probe.dispatcher_result.dropped - probe.dropped_at_start
                   if probe.dispatcher_result is not None else 0)
        setup_s = probe.t_start - T0
        compile_setup = sum(clock.between(float("-inf"), probe.t_start))
        compiles_window = len(clock.between(probe.t_start, probe.t_end))
        record = probe.record
        spans = probe.window_spans()
        probe.release()
        del clients, test
        gc.collect()

        t_ref = time.perf_counter()
        want = reference.replay(config, traffic, world, w0, record.arrivals,
                                sub["shuffle"])
        print(f"bench: setup {setup_s:.1f}s (world {t_world - T0:.1f}s, "
              f"warm pass to {probe.warm_updates} updates), window "
              f"{probe.window_s:.2f}s with {counters['updates']} updates and "
              f"{compiles_window} programs built in it, reference "
              f"{time.perf_counter() - t_ref:.1f}s", file=sys.stderr)
        w0_flat = reference.flatten(config, w0)
        values = correct.numbers(record.as_outputs(), want, w0_flat,
                                 reference.leaf_sizes(config))
        ok, checks = correct.verdict(values, limits)
        if keep is not None:
            keep.update(config=config, traffic=traffic, world=world, w0=w0,
                        record=record, want=want, w0_flat=w0_flat,
                        shuffle=sub["shuffle"])

        result = {"correct": bool(ok), "attempted": int(counters["updates"]),
                  "failed": int(dropped)}
        device["memory_peak_bytes"] = peak
        if not trace:
            metrics = {
                "updates_per_s": counters["updates"] / probe.window_s,
                "peak_hbm_gb": peak / 1e9,
                "setup_s": setup_s}
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            result["metrics"] = {
                m["name"]: {"value": metrics[m["name"]], "unit": units[m["name"]]}
                for m in cell_metrics(bench, cell["name"], "end_to_end")}
        else:
            from bench import tracing
            tr_data = tracing.load(tracing.xplane_path(trace_dir))
            t0, t1 = tr_data.window()
            ctx = Context(config=config, traffic=traffic, peaks=peaks,
                          params=flops.trained_params(config), t_start=probe.t_start,
                          t_end=probe.t_end, window_s=probe.window_s,
                          counters=counters, spans=spans,
                          compile_setup_s=compile_setup,
                          compiles_window=compiles_window, trace=tr_data)
            result["metrics"] = {}
            for m in cell_metrics(bench, cell["name"], "per_layer"):
                mod = load_metric(m["name"])
                if mod.UNIT != m["unit"]:
                    raise ValueError(f"{m['name']}: reader unit {mod.UNIT!r},"
                                     f" BENCHMARK.json {m['unit']!r}")
                v = mod.read(ctx)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": float(v),
                                                    "unit": m["unit"]}
            device["busy_s"] = tracing.busy_ns(tr_data) * 1e-9
            device["window_s"] = (t1 - t0) * 1e-9
            result["breakdown"] = {
                "device_ops": tracing.top_ops(tr_data),
                "idle_gaps": tracing.gaps_by_activity(tr_data)}
        result["device"] = device
        result["checks"] = checks
        return result
    finally:
        clock.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_benchmark()
        cell = find_cell(bench, args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          bench=bench)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
