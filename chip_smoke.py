"""Chip smoke test: the FedPSA asynchronous training path on a TPU.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # the mesh-sharded path vs one device

Drives the system through its normal entry points (``build_task`` and
``run_algorithm``, as ``repro.launch.train`` does) at the full published
width of the paper's CIFAR-10 CNN (d = 1,756,426), with random weights and
synthetic data made from a seed. Everything runs in this one process, which
holds the chip.

Phases (one chip):

1. the default JAX device is a TPU, and the Pallas kernels resolve to their
   compiled (not interpreted) mode;
2. kernel parity at this model's width against ``repro.kernels.ref``:
   ``buffer_agg`` at (L=5, d), ``sens_sketch`` on every parameter leaf, and
   ``grouped_matmul`` at the fc0 member shape with a valid mask, at the
   default and at f32 matmul precision; each compiled program must contain
   a ``tpu_custom_call``;
3. a fedpsa run on the cohort engine (50 clients, concurrency 0.2, 5 local
   epochs, batch 64, latency U(10, 500), Dirichlet alpha = 0.1 over 50,000
   samples) to a 2,000-unit virtual horizon;
4. the oracle world (the same protocol on 5,000 samples, so every client
   trains at most a few dozen local steps) to a short horizon on the cohort
   engine and on the sequential oracle: equal versions/dispatches/launched,
   digests within ``DIGEST_TOL`` of the model's movement; two policy
   faults (server_lr 0.99, no thermometer) run on the cohort engine must
   each land beyond ``DIGEST_TOL``.

With ``--chips 4`` only the sharded path runs: the oracle world on
``make_fed_mesh(4)`` (``ShardedPolicyServer`` plus data-parallel waves)
against the same run on one device, compared as in phase 4.

Progress goes to stdout and a JSON summary to ``chiprun_out/``. The last
line of stdout is ``{"ok": true, "device": {...}}`` only when every phase
passed; any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

MODEL = "paper-cifar10-cnn"
ALG = "fedpsa"
SAMPLES = 50_000
ALPHA = 0.1
CLIENTS = 50
SEED = 0
HORIZON = 2_000.0          # ~80 client updates at 10 in flight
# The oracle world. The engines compile a client's training into different
# programs (a vmapped wave vs one client at a time) that round differently,
# and local SGD on this model amplifies a rounding difference: on the CPU a
# one-ulp perturbation of the snapshot moves a 3,470-sample client's update
# by 4.8e-2 of its norm after 54 steps, as much as the engines differ there;
# on the TPU, at its default one-pass bf16 matmul precision, by 1e-1 after
# 11 steps (benchmarks/engine_drift.py, PERF.md). So the comparison runs
# the same protocol on a tenth of the data, where the largest client takes
# about 30 steps, not 270: on v5e the engine gap falls to a fifth of the
# smallest fault's reading (it is as large as the faults on the full world).
ORACLE_SAMPLES = 5_000
ORACLE_HORIZON = 600.0     # a few aggregations, sequential-cheap
# Digest agreement, relative to how far the model moved: per receive,
# ||digest_a - digest_b|| over the largest ||digest_b - digest(w0)|| of the
# run. v5e read the engine gap at 1.7e-2 (sharded vs one device on four
# chips: 2.6e-2) and the CONTROLS faults, run on the cohort engine, at
# 8.2e-2 (server_lr 0.99) and 1.6e-1 (no thermometer); the bound sits
# between, and phase 4 asserts every run that each fault still lands beyond
# it. A chip run is deterministic, so these readings repeat. Finer faults
# are the CPU golden suite's (1e-4 of ||w||).
DIGEST_TOL = 4e-2
CONTROLS = {"server_lr 0.99": {"server_lr": 0.99},
            "no thermometer": {"use_thermometer": False}}
KERNEL_RTOL = 1e-5
# grouped_matmul at the default precision, where the kernel and the XLA
# reference each take a one-pass bf16 product (v5e read 0.0: the same
# product); the limit leaves room for inputs rounded to 2^-9 differently,
# about 1e-3 of the largest output at K = 4,096
GMM_DEFAULT_RTOL = 1e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def compiled() -> tuple:
    """Backend compile seconds and programs built so far in this process:
    the program's ``compile`` counter (``repro.common.obs``)."""
    from repro.common import obs
    c = obs.totals()["counters"].get(obs.COMPILE, {})
    return c.get("seconds", 0.0), c.get("records", 0)


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def compiled_kernel(fn, *args):
    """Compile ``fn`` for the default device; insist on a Mosaic kernel."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{getattr(fn, '__name__', fn)}: no "
                             f"tpu_custom_call in the compiled program")
    return compiled


# ---------------------------------------------------------------------------
# Phase 1: device
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    import jax
    from repro.kernels.buffer_agg import resolve_interpret
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    log(f"phase 1 device: {info}")
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's default device is {dev.platform}")
    if len(devs) < chips:
        raise RuntimeError(f"asked for {chips} chips, JAX sees {len(devs)}")
    if resolve_interpret(None):
        raise RuntimeError("Pallas kernels would run in interpret mode")
    return info


# ---------------------------------------------------------------------------
# Phase 2: kernel parity at the model's width
# ---------------------------------------------------------------------------

def phase_kernels(cfg, params) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.common import tree as tu
    from repro.core import sketch as sk
    from repro.kernels import ref
    from repro.kernels.buffer_agg import buffer_agg_pallas
    from repro.kernels.grouped_matmul import grouped_matmul_pallas
    from repro.kernels.sens_sketch import sens_sketch_pallas

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(SEED)
    nk = lambda i: jax.random.fold_in(key, i)
    out = {}
    # references in true f32: TPU's default matmul precision is bf16
    exact = functools.partial(jax.default_matmul_precision, "float32")

    d = tu.FlatSpec(params).size
    L = 5
    w = jax.nn.softmax(jax.random.normal(nk(0), (L,)))
    g = jax.random.normal(nk(1), (d,))
    u = jax.random.normal(nk(2), (L, d))
    agg = compiled_kernel(functools.partial(buffer_agg_pallas,
                                            interpret=False), w, g, u)
    with exact():
        want = jax.jit(ref.buffer_agg_ref)(w, g, u)
    out["buffer_agg"] = rel_err(agg(w, g, u), want)

    errs = []
    k = 16
    for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
        n = int(leaf.size)
        th = jax.random.normal(nk(10 + 3 * i), (n,))
        gr = jax.random.normal(nk(11 + 3 * i), (n,))
        fi = jnp.abs(jax.random.normal(nk(12 + 3 * i), (n,)))
        seed = sk.leaf_seed_host(0, i)
        kern = compiled_kernel(functools.partial(
            sens_sketch_pallas, k=k, seed=seed, interpret=False), th, gr, fi)
        want = jax.jit(functools.partial(ref.sens_sketch_ref, k=k,
                                         seed=seed))(th, gr, fi)
        # a sketch row is a signed sum of s: scale its error by sum|s|
        s_l1 = float(jnp.sum(jnp.abs(gr * th - 0.5 * fi * th * th)))
        err = float(jnp.max(jnp.abs(kern(th, gr, fi) - want))) * math.sqrt(k)
        errs.append(err / s_l1)
    out["sens_sketch"] = max(errs)

    G, M, K, N = 4, 64, 4096, 384       # fc0 at batch 64, a 4-member bucket
    lhs = jax.random.normal(nk(3), (G, M, K))
    rhs = jax.random.normal(nk(4), (G, K, N)) / math.sqrt(K)
    valid = jnp.array([1.0, 1.0, 1.0, 0.0])
    limits = {"buffer_agg": KERNEL_RTOL, "sens_sketch": KERNEL_RTOL}
    # the kernel follows the caller's matmul precision: compare it with the
    # reference at the same precision, the default's and f32's
    for prec, ctx, limit in (("default", contextlib.nullcontext, GMM_DEFAULT_RTOL),
                             ("float32", exact, KERNEL_RTOL)):
        with ctx():
            gmm = compiled_kernel(functools.partial(
                grouped_matmul_pallas, interpret=False), lhs, rhs, valid)
            want = jax.jit(ref.grouped_matmul_ref)(lhs, rhs, valid)
        got = gmm(lhs, rhs, valid)
        if float(jnp.max(jnp.abs(got[3]))) != 0.0:
            raise AssertionError("grouped_matmul: masked group is not exact "
                                 "zero")
        out[f"grouped_matmul_{prec}"] = rel_err(got, want)
        limits[f"grouped_matmul_{prec}"] = limit

    log(f"phase 2 kernels ({time.perf_counter() - t0:.1f}s; max relative "
        f"error, compiled vs ref): {json.dumps(out)}")
    bad = {n: (e, limits[n]) for n, e in out.items() if not e <= limits[n]}
    if bad:
        raise AssertionError(f"kernel parity above its limit: {bad}")
    return out


# ---------------------------------------------------------------------------
# Phases 3-4: the federated run
# ---------------------------------------------------------------------------

def build_world(samples: int):
    import jax
    import numpy as np
    from repro.common import tree as tu
    from repro.federated import SimConfig
    from repro.federated.simulator import make_digest_fn
    from repro.launch.train import build_task
    from repro.models import model as model_lib

    t0 = time.perf_counter()
    cfg, clients, test, calib = build_task(MODEL, samples, ALPHA, CLIENTS,
                                           SEED)
    params = model_lib.init_params(jax.random.PRNGKey(SEED), cfg)
    spec = tu.FlatSpec(params)
    sizes = np.array([len(c) for c in clients])
    x0 = clients[0].data
    # the cohort engine's padded (C, n_max, ...) f32 x and int32 y slab
    row = 4 * (int(np.prod(x0.x.shape[1:])) + int(np.prod(x0.y.shape[1:])))
    slab = len(clients) * int(sizes.max()) * row
    sim = SimConfig()
    world = dict(cfg=cfg, clients=clients, test=test, calib=calib,
                 params=params, digest0=make_digest_fn(spec.size)(
                     np.asarray(spec.flatten(params))[None])[0])
    info = {"samples": samples, "build_s": time.perf_counter() - t0,
            "client_sizes_min_max": [int(sizes.min()), int(sizes.max())],
            "max_local_steps": sim.local_epochs
            * (int(sizes.max()) // sim.batch_size),
            "slab_bytes": slab}
    log(f"world: {MODEL}, {len(clients)} clients: {json.dumps(info)}")
    return world, info


def run(world, horizon: float, engine: str = "cohort", mesh=None,
        record: bool = False, psa: dict | None = None):
    from repro.core import PSAConfig
    from repro.federated import SimConfig, run_algorithm

    sim = SimConfig(num_clients=CLIENTS, horizon=horizon, seed=SEED,
                    engine=engine, mesh=mesh, record_trajectory=record)
    return run_algorithm(ALG, world["cfg"], world["params"],
                         world["clients"], world["test"], sim,
                         psa_cfg=PSAConfig(**(psa or {})),
                         calib_batch=world["calib"])


def timed_run(label: str, world, horizon: float, **kw):
    c0, n0 = compiled()
    t0 = time.perf_counter()
    res = run(world, horizon, **kw)
    wall = time.perf_counter() - t0
    c1, n1 = compiled()
    info = {"engine": res.engine, "wall_s": wall, "compile_s": c1 - c0,
            "compiles": n1 - n0, "versions": res.versions,
            "dispatches": res.dispatches, "launched": res.launched,
            "cohorts": res.cohorts, "final_accuracy": res.final_accuracy,
            "eval_ticks": len(res.times)}
    if res.dispatches:
        info["updates_per_s"] = res.dispatches / wall
    log(f"{label}: {json.dumps(info)}")
    return res, info


def check_main_run(res) -> None:
    if res.engine != "cohort":
        raise AssertionError(f"engine resolved to {res.engine!r}")
    if res.versions <= 0:
        raise AssertionError("no aggregation was applied")
    if len(res.times) < 2 or not all(map(math.isfinite, res.accuracies)):
        raise AssertionError(f"bad learning curve {res.accuracies}")


def compare(a, b, world, label: str) -> dict:
    """Equal event counts; the digest gap per receive, relative to how far
    ``b``'s model moved from the initial one over the run."""
    import numpy as np
    for f in ("versions", "dispatches", "launched"):
        if getattr(a, f) != getattr(b, f):
            raise AssertionError(f"{label}: {f} {getattr(a, f)} != "
                                 f"{getattr(b, f)}")
    da, db = np.asarray(a.digests), np.asarray(b.digests)
    if da.shape != db.shape or not da.size:
        raise AssertionError(f"{label}: digest shapes {da.shape} {db.shape}")
    move = float(np.max(np.linalg.norm(db - world["digest0"], axis=1)))
    err = np.linalg.norm(da - db, axis=1) / move
    out = {"receives": int(len(err)), "movement": move,
           "max_digest_err": float(err.max()),
           "digest_err": [float(f"{e:.3g}") for e in err],
           "max_err_of_w_norm": float(np.max(np.abs(da - db)
                                             / np.abs(db[:, :1]))),
           "final_accuracy": [a.final_accuracy, b.final_accuracy]}
    log(f"{label}: {json.dumps(out)}")
    return out


def agree(out: dict, label: str) -> None:
    if not out["max_digest_err"] <= DIGEST_TOL:
        raise AssertionError(f"{label}: digests differ by "
                             f"{out['max_digest_err']:.3g} of the movement "
                             f"(limit {DIGEST_TOL})")


def peak_bytes() -> dict:
    import jax
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()}


def one_chip(summary: dict) -> None:
    world, summary["world"] = build_world(SAMPLES)
    summary["kernels"] = phase_kernels(world["cfg"], world["params"])
    res, summary["run"] = timed_run("phase 3 cohort run", world,
                                    HORIZON)
    check_main_run(res)
    summary["peak_bytes_in_use"] = peak_bytes()
    log(f"peak device memory: {summary['peak_bytes_in_use']}")
    del world, res
    oracle, summary["oracle_world"] = build_world(ORACLE_SAMPLES)
    coh, summary["oracle_cohort"] = timed_run(
        "phase 4 cohort", oracle, ORACLE_HORIZON, record=True)
    seq, summary["oracle_sequential"] = timed_run(
        "phase 4 sequential", oracle, ORACLE_HORIZON,
        engine="sequential", record=True)
    if coh.versions <= 0:
        raise AssertionError("phase 4 horizon applied no aggregation")
    label = "phase 4 cohort vs sequential"
    summary["oracle"] = compare(coh, seq, oracle, label)
    summary["controls"] = {}
    for name, psa in CONTROLS.items():
        ctl, _ = timed_run(f"phase 4 fault ({name})", oracle,
                           ORACLE_HORIZON, record=True, psa=psa)
        summary["controls"][name] = compare(
            ctl, seq, oracle, f"phase 4 fault ({name}) vs sequential")
    agree(summary["oracle"], label)
    for name, out in summary["controls"].items():
        if not out["max_digest_err"] > DIGEST_TOL:
            raise AssertionError(f"phase 4: the fault '{name}' reads "
                                 f"{out['max_digest_err']:.3g}, within the "
                                 f"limit {DIGEST_TOL}: the check is blind")


def four_chips(summary: dict) -> None:
    from repro.launch.mesh import make_fed_mesh
    world, summary["world"] = build_world(ORACLE_SAMPLES)
    sharded, summary["sharded"] = timed_run(
        "sharded run (4 chips)", world, ORACLE_HORIZON,
        mesh=make_fed_mesh(4), record=True)
    check_main_run(sharded)
    single, summary["single"] = timed_run(
        "single-device run", world, ORACLE_HORIZON, record=True)
    summary["peak_bytes_in_use"] = peak_bytes()
    log(f"peak device memory: {summary['peak_bytes_in_use']}")
    label = "sharded vs single device"
    summary["sharded_vs_single"] = compare(sharded, single, world, label)
    agree(summary["sharded_vs_single"], label)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    summary = {"chips": args.chips}
    try:
        sys.path.insert(0, os.path.join(HERE, "src"))
        from repro.launch.compile_cache import enable_compile_cache
        summary["compile_cache"] = enable_compile_cache()
        t0 = time.perf_counter()
        summary["device"] = phase_device(args.chips)
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    try:
        c0, n0 = compiled()
        (one_chip if args.chips == 1 else four_chips)(summary)
        summary["total_s"] = time.perf_counter() - t0
        c1, n1 = compiled()
        summary["compile_s"], summary["compiles"] = c1 - c0, n1 - n0
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    finally:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, f"chip_smoke_{args.chips}.json"),
                  "w") as f:
            json.dump(summary, f, indent=1, default=str)
    from repro.common import obs
    summary["obs"] = obs.totals()
    log(f"total {summary['total_s']:.1f}s, of which backend compile "
        f"{summary['compile_s']:.1f}s in {summary['compiles']} programs; "
        f"the program's spans and counters:\n{obs.summary()}")
    print(json.dumps({"ok": True, "device": summary["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
