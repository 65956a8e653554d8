"""Event-driven virtual-time AFL simulator (FLGO-style: 86,400 units/day).

Asynchronous runners keep ``concurrency`` clients training at all times: a
heap of completion events; on completion the server ingests the update, a
new client is sampled and dispatched with the *current* global model, and
the learning curve is sampled on a fixed virtual-time grid. The synchronous
FedAvg runner advances rounds at the pace of each round's slowest client —
exactly the straggler behaviour the paper contrasts against.

Two client engines drive the same event semantics:

``cohort`` (default)  completions drain in device batches. Every event's
    training depends only on its dispatch snapshot, so all events due before
    the earliest possible completion of any re-dispatch (``t_first +
    latency_lo``) form a *wave* that trains as ONE compiled call
    (``federated.cohort.CohortEngine`` — vmap over clients, a loop over local
    steps, flat parameter layout end to end: dispatch snapshots are the
    server's flat (d,) vector, never a pytree). Receives then apply strictly
    in completion order, so the receive order, per-dispatch lr/seed
    assignment, and RNG streams are identical to the sequential engine.

``sequential``  the legacy reference loop: one ``client.local_update``
    (python loop of per-batch jit calls) per completion. Kept as the
    numerical oracle the batched engine is pinned against.

The paper's defaults (§6.1): 50 clients, 20% concurrency/sampling, 5 local
epochs, batch 64, SGD lr 0.01 with x0.999 decay per (dispatch) round,
latency ~ U(10, 500). Client availability (FLGo-style intermittent
dropouts) is modelled per dispatch: a failed dispatch holds its concurrency
slot for the full response time, then re-dispatches without a receive.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import obs
from repro.common import tree as tu
from repro.core import psa as psa_lib
from repro.data.loader import ClientDataset, ClientSlabStore, StackedClients
from repro.federated import client as client_lib
from repro.federated import servers as servers_lib
from repro.federated.cohort import CohortEngine, StreamingCohortEngine
from repro.federated.latency import STREAM_SYNC_CHOICE, _subseed
from repro.federated.scheduler import Dispatcher, make_scheduler, make_streams
from repro.federated.timeline import Timeline, _Event
from repro.models import model as model_lib
from repro.models import registry
from repro.models.config import ModelConfig

ENGINES = ("cohort", "sequential")

_FALLBACK_WARNED = set()


def _timeline_seed(sim: "SimConfig") -> int:
    """The seed driving the EVENT TIMELINE (latency, client sampling,
    availability) — ``sim.seed`` unless ``sim.timeline_seed`` splits it."""
    return sim.seed if sim.timeline_seed is None else sim.timeline_seed


def _resolve_engine(sim: "SimConfig", cfg: ModelConfig) -> str:
    """Validate ``sim.engine`` and pick the engine that can train ``cfg``.

    The cohort engine compiles any family in the model-family registry
    (``models.registry``); unregistered families fall back to the sequential
    per-client loop (the generic ``client.local_update``) rather than
    crashing on the default ``engine="cohort"`` — with a one-time warning,
    because silently comparing a cohort run against a sequential fallback
    would corrupt benchmarks. The engine actually used is recorded on
    ``SimResult.engine``.
    """
    if sim.engine not in ENGINES:
        raise ValueError(f"unknown engine {sim.engine!r}; known: {ENGINES}")
    if sim.engine == "cohort" and not registry.is_registered(cfg.family):
        if cfg.family not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(cfg.family)
            warnings.warn(
                f"model family {cfg.family!r} is not in the model-family "
                f"registry (registered: {registry.registered_families()}); "
                f"engine='cohort' falls back to the sequential loop for it. "
                f"Register the family (models/registry.py) to compile it.",
                RuntimeWarning, stacklevel=3)
        return "sequential"
    return sim.engine


@dataclass
class SimConfig:
    num_clients: int = 50
    concurrency: float = 0.2          # fraction of clients training at once
    local_epochs: int = 5
    batch_size: int = 64
    lr: float = 0.01
    lr_decay: float = 0.999
    horizon: float = 86_400.0         # virtual time units (1 day default)
    eval_every: float = 2_000.0
    latency_kind: str = "uniform"
    latency_lo: float = 10.0
    latency_hi: float = 500.0
    availability_kind: str = "always"  # see latency.per_client_availability
    dropout_rate: float = 0.0          # per-dispatch failure rate when enabled
    # Dispatch policy — who to dispatch and when a freed slot relaunches
    # (federated.scheduler): "uniform" (historical immediate-refill rule,
    # golden-pinned), "period" (FLGo-style period-triggered sampling),
    # "staleness" (CSMAAFL-style utility/staleness-weighted selection).
    # ``scheduler_params`` passes scheduler keyword overrides (e.g.
    # {"period": 40.0} or {"staleness_weight": 2.0}).
    scheduler: str = "uniform"
    scheduler_params: Optional[dict] = None
    seed: int = 0
    # The seed is split along the sweep-lane contract: ``timeline_seed``
    # drives everything that shapes the EVENT TIMELINE (latency draws,
    # client sampling, availability) while ``seed`` keeps driving the
    # model/data side (client batch shuffles). None = use ``seed`` for both
    # (the historical behavior). run_sweep shares one timeline across all
    # lanes and varies only the per-lane model/data seeds.
    timeline_seed: Optional[int] = None
    # Periodic full-fidelity snapshots (repro.checkpoint.store layout):
    # every ``checkpoint_every`` virtual-time units the simulator persists
    # the ServerState, the host RNG streams, the in-flight event timeline and
    # the metric/digest streams under ``checkpoint_dir``. ``resume=True``
    # restores the latest snapshot and reproduces the remaining trajectory
    # exactly. Single runs only (sweeps are not checkpointed).
    checkpoint_dir: Optional[str] = None
    checkpoint_every: float = 0.0
    resume: bool = False
    eval_batches: int = 8
    eval_batch_size: int = 512
    engine: str = "cohort"             # "cohort" (batched) | "sequential"
    max_cohort: int = 256              # cap on one wave's device batch
    # Member-math routing inside the cohort engines (models.member_math):
    # "vmap" keeps the per-member dot_general HLO the golden digests pin;
    # "grouped" collapses each wave's dense layers into single Pallas
    # grouped-GEMM launches over the stacked member axis (compiled on TPU,
    # interpret fallback elsewhere) — 1e-5-parity-pinned against "vmap".
    member_kernel: str = "vmap"        # "vmap" | "grouped"
    # Streaming client slabs (population scale): ``shard_size > 0`` switches
    # the cohort engine from the monolithic (C, n_max, ...) device slab to
    # fixed-size client shards uploaded lazily per wave behind a bounded LRU
    # (``data.loader.ClientSlabStore``); host+device data memory is then
    # O(shard_cache * shard_size * n_max), independent of C. Passing a lazy
    # population (e.g. ``data.synthetic.SyntheticPopulation``) instead of a
    # client-dataset list forces the streaming path (auto shard size when 0).
    shard_size: int = 0                # clients per shard; 0 = monolithic
    shard_cache: int = 32              # max resident shards (LRU)
    shard_promote: int = 8             # cache a shard once a wave wants
                                       # >= this many of its clients
    # Async shard prefetch (streaming engine only): right after a wave's
    # replacement dispatches are inserted, peek the NEXT wave's member set
    # off the timeline (Timeline.peek_wave_cids) and overlap its host
    # materialization + H2D upload with the current device work on the
    # store's single background worker. Pure hint: rows are a pure function
    # of cid, so results are bit-identical with prefetch on or off (see
    # ARCHITECTURE.md "dispatch pipeline contract").
    prefetch: bool = False
    # Layout: with a mesh, the policy server shards ServerState over the
    # mesh's flat-parameter axis (servers.ShardedPolicyServer) and the
    # cohort engine trains waves data-parallel over the client axis; rules
    # (default common.sharding.FEDERATED_RULES) map the logical
    # param_shard/cohort axes onto mesh axes. None = single-device layout.
    mesh: Optional[object] = None      # jax.sharding.Mesh
    rules: Optional[object] = None     # common.sharding.LogicalRules
    # Record a per-receive (||w||, probe·w) digest stream of the global
    # model — the golden-trajectory fingerprint (tests/test_golden.py).
    record_trajectory: bool = False


@dataclass
class SimResult:
    times: List[float] = field(default_factory=list)
    accuracies: List[float] = field(default_factory=list)
    final_accuracy: float = 0.0
    versions: int = 0
    dispatches: int = 0
    launched: int = 0                 # total dispatch calls (incl. in flight)
    dropped: int = 0                  # dispatches lost to client unavailability
    cohorts: int = 0                  # device batches the cohort engine ran
    engine: str = ""                  # engine actually used ("cohort" may
                                      # have resolved to "sequential")
    server_log: List[dict] = field(default_factory=list)
    receive_log: List[dict] = field(default_factory=list)
    digests: List[List[float]] = field(default_factory=list)

    @property
    def aulc(self) -> float:
        """Area under the learning curve normalized by the run's actual
        time span, so the unit (mean accuracy over the run) is comparable
        across horizons — matching the paper's Table 3 convention.

        NaN (not 0.0) when the curve has fewer than two points or spans no
        time (e.g. ``eval_every`` > horizon): there is no area to report,
        and a silent zero would poison AULC comparison tables."""
        if len(self.times) < 2:
            return float("nan")
        t = np.asarray(self.times)
        a = np.asarray(self.accuracies)
        span = float(t[-1] - t[0])
        if span <= 0.0:
            return float("nan")
        return float(np.trapezoid(a, t) / span)


# Cross-run jit reuse: evaluation and sketch closures are deterministic in
# (model, dataset object, config), so cache them instead of re-jitting per
# run. The anchor object is part of the key by id() and is also stored in
# the value: the strong reference keeps the id valid for the cache's
# lifetime, and the identity check guards against id reuse.
_EVAL_CACHE: Dict[tuple, tuple] = {}
_EVAL_LANES_CACHE: Dict[tuple, tuple] = {}
_SKETCH_FN_CACHE: Dict[tuple, tuple] = {}
_SKETCH_FLAT_CACHE: Dict[tuple, tuple] = {}
_SKETCH_LANES_CACHE: Dict[tuple, tuple] = {}


def _memo_identity(cache: Dict[tuple, tuple], key: tuple, anchor, build):
    hit = cache.get(key + (id(anchor),))
    if hit is not None and hit[0] is anchor:
        return hit[1]
    fn = build()
    cache[key + (id(anchor),)] = (anchor, fn)
    return fn


def _make_eval(cfg: ModelConfig, test_ds, sim: SimConfig):
    # the registry entry (None for unregistered families) is part of the
    # key so register_family(..., override=True) invalidates the closure
    fam = (registry.get_family(cfg)
           if registry.is_registered(cfg.family) else None)
    return _memo_identity(
        _EVAL_CACHE, (cfg, sim.eval_batches, sim.eval_batch_size, fam),
        test_ds, lambda: _build_eval(cfg, test_ds, sim))


def _build_eval(cfg: ModelConfig, test_ds, sim: SimConfig):
    from repro.common.sharding import SINGLE_DEVICE_RULES as R

    rng = np.random.RandomState(1234)
    n = len(test_ds)
    bs = min(sim.eval_batch_size, n)
    idxs = [rng.choice(n, size=bs, replace=False) for _ in range(sim.eval_batches)]
    if registry.is_registered(cfg.family):
        fam = registry.get_family(cfg)
        batches = [fam.batch_fn(test_ds.x[ix], test_ds.y[ix]) for ix in idxs]

        @jax.jit
        def acc1(params, batch):
            return fam.eval_accuracy(params, batch, cfg, R)
    else:
        # unregistered family on the sequential fallback: the legacy argmax
        # eval (model_lib.predict raises a clear error for families it
        # cannot score — register the family to plug in a metric)
        batches = [{"x": jnp.asarray(test_ds.x[ix]),
                    "y": jnp.asarray(test_ds.y[ix])} for ix in idxs]

        @jax.jit
        def acc1(params, batch):
            return jnp.mean((model_lib.predict(params, batch["x"], cfg)
                             == batch["y"]).astype(jnp.float32))

    def evaluate(params) -> float:
        return float(np.mean([float(acc1(params, b)) for b in batches]))

    return evaluate


def _make_eval_lanes(cfg: ModelConfig, test_ds, sim: SimConfig,
                     spec: tu.FlatSpec):
    fam = registry.get_family(cfg)
    return _memo_identity(
        _EVAL_LANES_CACHE,
        (cfg, sim.eval_batches, sim.eval_batch_size, fam, spec),
        test_ds, lambda: _build_eval_lanes(cfg, test_ds, sim, spec))


def _build_eval_lanes(cfg: ModelConfig, test_ds, sim: SimConfig,
                      spec: tu.FlatSpec):
    """Lane-batched evaluation: (S, d) flat lane models -> (S,) accuracies,
    one vmapped call per eval batch. Same RandomState(1234) batch draw as
    ``_build_eval``, so a lane's accuracy equals the standalone run's."""
    from repro.common.sharding import SINGLE_DEVICE_RULES as R

    fam = registry.get_family(cfg)
    rng = np.random.RandomState(1234)
    n = len(test_ds)
    bs = min(sim.eval_batch_size, n)
    idxs = [rng.choice(n, size=bs, replace=False)
            for _ in range(sim.eval_batches)]
    batches = [fam.batch_fn(test_ds.x[ix], test_ds.y[ix]) for ix in idxs]

    acc1 = jax.jit(jax.vmap(
        lambda vec, batch: fam.eval_accuracy(spec.unflatten(vec), batch,
                                             cfg, R),
        in_axes=(0, None)))

    def evaluate(flat_stack) -> np.ndarray:
        return np.mean([np.asarray(acc1(flat_stack, b)) for b in batches],
                       axis=0)

    return evaluate


def make_sketch_fn(cfg: ModelConfig, calib_batch: dict, psa_cfg: psa_lib.PSAConfig):
    return _memo_identity(
        _SKETCH_FN_CACHE, (cfg, psa_cfg), calib_batch,
        lambda: _build_sketch_fn(cfg, calib_batch, psa_cfg))


def _build_sketch_fn(cfg: ModelConfig, calib_batch: dict, psa_cfg: psa_lib.PSAConfig):
    calib = {k: jnp.asarray(v) for k, v in calib_batch.items()}
    from repro.common.sharding import SINGLE_DEVICE_RULES as R

    def loss(params, batch):
        return model_lib.loss_fn(params, batch, cfg, R)

    @jax.jit
    def fn(params):
        return psa_lib.client_sketch(loss, params, calib, psa_cfg)

    return fn


def make_sketch_fn_flat(cfg: ModelConfig, calib_batch: dict,
                        psa_cfg: psa_lib.PSAConfig, spec: tu.FlatSpec,
                        mesh=None, axis: Optional[str] = None):
    return _memo_identity(
        _SKETCH_FLAT_CACHE, (cfg, psa_cfg, spec, mesh, axis), calib_batch,
        lambda: _build_sketch_fn_flat(cfg, calib_batch, psa_cfg, spec,
                                      mesh, axis))


def _build_sketch_fn_flat(cfg: ModelConfig, calib_batch: dict,
                          psa_cfg: psa_lib.PSAConfig, spec: tu.FlatSpec,
                          mesh=None, axis: Optional[str] = None):
    """Batched sketch over flat client models: (B, d) -> (B, k), one jitted
    vmap call per wave (row counts bucketed like the engine).

    On a mesh the call runs under ``shard_map``, because the compiled Pallas
    sketch kernel cannot be partitioned automatically: each device sketches
    its rows of the cohort ``axis`` where the engine splits the wave
    (``cohort.wave_axis``), else every device sketches all rows."""
    calib = {k: jnp.asarray(v) for k, v in calib_batch.items()}
    from repro.common.sharding import SINGLE_DEVICE_RULES as R

    def loss(params, batch):
        return model_lib.loss_fn(params, batch, cfg, R)

    rows = jax.vmap(
        lambda vec: psa_lib.client_sketch(loss, spec.unflatten(vec), calib,
                                          psa_cfg))
    if mesh is None:
        batched = {None: jax.jit(rows)}
    else:
        from jax.sharding import PartitionSpec as P
        batched = {ax: jax.jit(jax.shard_map(
            rows, mesh=mesh, in_specs=P(ax), out_specs=P(ax),
            check_vma=False)) for ax in {None, axis}}
    from repro.federated.cohort import bucket_size, wave_axis
    data_kind = registry.get_family(cfg).data_kind

    def fn(w_stack: jnp.ndarray) -> jnp.ndarray:
        B = int(w_stack.shape[0])
        # same family-dependent bucket grid as the engine
        Bp = bucket_size(B, data_kind)
        obs.record("sketch.rows", rows=Bp)
        if Bp > B:
            w_stack = jnp.concatenate(
                [w_stack, jnp.zeros((Bp - B, w_stack.shape[1]), w_stack.dtype)])
        return batched[wave_axis(mesh, axis, Bp)](w_stack)[:B]

    return fn


def make_sketch_fn_lanes(cfg: ModelConfig, calib_batch: dict,
                         psa_cfg: psa_lib.PSAConfig, spec: tu.FlatSpec):
    return _memo_identity(
        _SKETCH_LANES_CACHE, (cfg, psa_cfg, spec), calib_batch,
        lambda: _build_sketch_fn_lanes(cfg, calib_batch, psa_cfg, spec))


def _build_sketch_fn_lanes(cfg: ModelConfig, calib_batch: dict,
                           psa_cfg: psa_lib.PSAConfig, spec: tu.FlatSpec):
    """Lane-batched client sketches: (S, B, d) -> (S, B, k) with one nested
    vmap call per wave, member axis bucketed like the engine."""
    calib = {k: jnp.asarray(v) for k, v in calib_batch.items()}
    from repro.common.sharding import SINGLE_DEVICE_RULES as R

    def loss(params, batch):
        return model_lib.loss_fn(params, batch, cfg, R)

    batched = jax.jit(jax.vmap(jax.vmap(
        lambda vec: psa_lib.client_sketch(loss, spec.unflatten(vec), calib,
                                          psa_cfg))))
    from repro.federated.cohort import bucket_size
    data_kind = registry.get_family(cfg).data_kind

    def fn(w_stack: jnp.ndarray) -> jnp.ndarray:
        S, B = int(w_stack.shape[0]), int(w_stack.shape[1])
        Bp = bucket_size(B, data_kind)
        if Bp > B:
            w_stack = jnp.concatenate(
                [w_stack, jnp.zeros((S, Bp - B, w_stack.shape[2]),
                                    w_stack.dtype)], axis=1)
        return batched(w_stack)[:, :B]

    return fn


# Trajectory digest: one (||w||_2, probe·w) pair per applied receive — a
# 2-float fingerprint of the full (d,) global vector that any execution path
# (sequential, cohort, sharded) can be compared on within float tolerance.
_DIGEST_SEED = 0xD16E57
_DIGEST_FN_CACHE: Dict[int, Callable] = {}


def make_digest_fn(d: int) -> Callable:
    """(B, d) -> (B, 2) numpy digest with the fixed probe vector for d.
    Host-side on purpose: the rows are transferred for recording anyway,
    and a jitted variant would recompile for every distinct wave size."""
    fn = _DIGEST_FN_CACHE.get(d)
    if fn is None:
        probe = np.random.RandomState(_DIGEST_SEED).randn(d).astype(
            np.float32).astype(np.float64)

        def fn(rows):
            # f32 rows, f64 sums: numpy orders a reduction's partial sums
            # by the array's shape, which in f32 moved a row's digest by
            # ~1e-5 between batches; in f64 the batch shows at ~1e-14
            rows = np.asarray(rows, np.float32).astype(np.float64)
            return np.stack([np.sqrt(np.sum(rows * rows, axis=-1)),
                             rows @ probe], axis=-1)

        _DIGEST_FN_CACHE[d] = fn
    return fn


# ---------------------------------------------------------------------------
# Simulator checkpointing (SimConfig.checkpoint_dir / checkpoint_every)
# ---------------------------------------------------------------------------
# A snapshot is taken at wave boundaries (timeline complete, all receives
# applied): the ServerState leaves, the three host RNG streams (dispatch,
# latency jitter, availability draws), the in-flight events with their
# dispatch snapshots materialized to one (n, d) stack, and the
# metric/digest/receive-log streams — enough to restore mid-run and
# reproduce the REMAINING digest stream exactly. ``server.log`` (the
# policy's rendered per-update log) is the one stream NOT persisted: a
# resumed run's copy covers only the post-resume segment.

def _rng_pack(rng: np.random.RandomState) -> dict:
    kind, keys, pos, has_gauss, cached = rng.get_state()
    assert kind == "MT19937"
    return {"keys": np.asarray(keys, np.uint32),
            "pos": np.int64(pos), "has_gauss": np.int64(has_gauss),
            "cached": np.float64(cached)}


def _rng_unpack(rng: np.random.RandomState, packed: dict) -> None:
    rng.set_state(("MT19937", np.asarray(packed["keys"], np.uint32),
                   int(packed["pos"]), int(packed["has_gauss"]),
                   float(packed["cached"])))


def _event_snapshot_vec(ev: "_Event", spec: tu.FlatSpec) -> np.ndarray:
    """Materialize one in-flight event's dispatch snapshot as a flat (d,)
    row (resolving cohort-engine ``(source, row)`` references and
    flattening sequential-engine pytrees)."""
    s = ev.snapshot
    if isinstance(s, tuple):
        return np.asarray(s[0][s[1]])
    if isinstance(s, jnp.ndarray) and s.ndim == 1:
        return np.asarray(s)
    return np.asarray(spec.flatten(s))


def _ckpt_state_sched(scheduler) -> bool:
    """Whether snapshots for this run carry a scheduler-state subtree.
    Stateless schedulers contribute nothing (their tree layout — and thus
    old snapshots — stays unchanged); stateful ones must have opted in via
    ``checkpoint_state`` (run_async rejects the rest up front)."""
    return not scheduler.stateless and scheduler.checkpoint_state


def _ckpt_save(sim: "SimConfig", server, rng, latency, avail_rng, timeline,
               scheduler, result: "SimResult", t: float, next_eval: float,
               seq: int) -> str:
    from repro.checkpoint import store
    spec = server.policy.spec
    events = timeline.events()
    tree = {
        "server": {f"{i:04d}": np.asarray(x) for i, x in
                   enumerate(jax.tree_util.tree_leaves(server.state))},
        "events": {
            "t_done": np.asarray([e.t_done for e in events], np.float64),
            "seq": np.asarray([e.seq for e in events], np.int64),
            "cid": np.asarray([e.cid for e in events], np.int64),
            "version": np.asarray([e.version for e in events], np.int64),
            "ok": np.asarray([e.ok for e in events], bool),
            "snapshots": np.stack([_event_snapshot_vec(e, spec)
                                   for e in events]),
        },
        "rng": _rng_pack(rng),
        "lat_rng": _rng_pack(latency.rng),
        "avail_rng": _rng_pack(avail_rng),
        "counters": np.asarray(
            [t, next_eval, seq, result.dispatches, result.launched,
             result.dropped, result.cohorts, server.version], np.float64),
        "times": np.asarray(result.times, np.float64),
        "accuracies": np.asarray(result.accuracies, np.float64),
        "digests": np.asarray(result.digests, np.float64).reshape(-1, 2),
        "receive_log": {
            "t": np.asarray([r["t"] for r in result.receive_log], np.float64),
            "tau": np.asarray([r["tau"] for r in result.receive_log],
                              np.int64),
            "client": np.asarray([r["client"] for r in result.receive_log],
                                 np.int64),
        },
    }
    if _ckpt_state_sched(scheduler):
        tree["scheduler"] = scheduler.state_arrays()
    return store.save_pytree(tree, sim.checkpoint_dir, step=result.dispatches)


def _ckpt_like(server, scheduler) -> dict:
    """A structure template for ``store.load_pytree`` (shapes are ignored by
    the restore — only the tree structure and leaf names must match)."""
    z = np.zeros((0,))
    sched_tree = ({"scheduler": {k: z for k in scheduler.state_arrays()}}
                  if _ckpt_state_sched(scheduler) else {})
    return {
        **sched_tree,
        "server": {f"{i:04d}": z for i in
                   range(len(jax.tree_util.tree_leaves(server.state)))},
        "events": {k: z for k in ("t_done", "seq", "cid", "version", "ok",
                                  "snapshots")},
        "rng": {k: z for k in ("keys", "pos", "has_gauss", "cached")},
        "lat_rng": {k: z for k in ("keys", "pos", "has_gauss", "cached")},
        "avail_rng": {k: z for k in ("keys", "pos", "has_gauss", "cached")},
        "counters": z, "times": z, "accuracies": z, "digests": z,
        "receive_log": {k: z for k in ("t", "tau", "client")},
    }


def _ckpt_restore(sim: "SimConfig", server, rng, latency, avail_rng,
                  timeline, scheduler, result: "SimResult", batched: bool):
    """Restore the latest snapshot under ``sim.checkpoint_dir`` into the
    live run, returning ``(t, next_eval, seq)`` — or None when there is no
    snapshot to resume from (the run then starts fresh)."""
    from repro.checkpoint import store
    step = store.latest_step(sim.checkpoint_dir)
    if step is None:
        return None
    tree = store.load_pytree(sim.checkpoint_dir,
                             _ckpt_like(server, scheduler), step)
    if _ckpt_state_sched(scheduler):
        scheduler.load_state_arrays(tree["scheduler"])
    treedef = jax.tree_util.tree_structure(server.state)
    leaves = [jnp.asarray(tree["server"][f"{i:04d}"])
              for i in range(treedef.num_leaves)]
    server.state = jax.tree_util.tree_unflatten(treedef, leaves)
    _rng_unpack(rng, tree["rng"])
    _rng_unpack(latency.rng, tree["lat_rng"])
    _rng_unpack(avail_rng, tree["avail_rng"])
    (t, next_eval, seq, dispatches, launched, dropped, cohorts,
     version) = (float(v) for v in tree["counters"])
    server._version = int(version)
    ev = tree["events"]
    snaps = jnp.asarray(ev["snapshots"], jnp.float32)
    unflatten = (None if batched
                 else tu.jit_unflatten(server.policy.spec))
    timeline.clear()
    n = len(ev["seq"])
    snap_refs = [(snaps, i) if batched else unflatten(snaps[i])
                 for i in range(n)]
    timeline.extend_arrays(ev["t_done"], ev["seq"], ev["cid"],
                           ev["version"], ev["ok"], snap_refs)
    result.dispatches = int(dispatches)
    result.launched = int(launched)
    result.dropped = int(dropped)
    result.cohorts = int(cohorts)
    result.times = [float(x) for x in tree["times"]]
    result.accuracies = [float(x) for x in tree["accuracies"]]
    result.digests = [list(row) for row in tree["digests"]]
    rl = tree["receive_log"]
    result.receive_log = [
        {"t": float(rl["t"][i]), "tau": int(rl["tau"][i]),
         "client": int(rl["client"][i])} for i in range(len(rl["t"]))]
    return float(t), float(next_eval), int(seq)


def _data_sizes(client_datasets) -> np.ndarray:
    """(C,) per-client sample counts — reading ``.sizes`` when the client
    source is a lazy population (no per-client dataset objects to len())."""
    sizes = getattr(client_datasets, "sizes", None)
    if sizes is not None:
        return np.asarray(sizes, np.float64)
    return np.array([len(d) for d in client_datasets], np.float64)


def _wants_streaming(sim: "SimConfig", client_datasets) -> bool:
    """The streaming slab path: explicitly via ``sim.shard_size > 0``, or
    implicitly when the client source is a lazy population object rather
    than a list of materialized ``ClientDataset``s."""
    return sim.shard_size > 0 or not isinstance(client_datasets, (list, tuple))


def _make_cohort_engine(cfg, client_datasets, spec, template_params,
                        sim: "SimConfig", *, prox: float = 0.0,
                        align: float = 0.0):
    """Build the wave-training engine: the monolithic-slab ``CohortEngine``
    by default, the shard-streaming variant when configured (see
    ``SimConfig.shard_size``)."""
    if _wants_streaming(sim, client_datasets):
        if sim.mesh is not None:
            raise ValueError("streaming client slabs are single-device; "
                             "drop SimConfig.mesh or shard_size")
        store = ClientSlabStore.build(
            client_datasets, shard_size=sim.shard_size,
            cache_shards=sim.shard_cache, promote=sim.shard_promote)
        return StreamingCohortEngine(
            cfg, store, spec, template_params,
            local_epochs=sim.local_epochs, batch_size=sim.batch_size,
            prox=prox, align=align, member_kernel=sim.member_kernel)
    stacked = StackedClients.from_datasets(client_datasets)
    return CohortEngine(cfg, stacked, spec, template_params,
                        local_epochs=sim.local_epochs,
                        batch_size=sim.batch_size, prox=prox, align=align,
                        mesh=sim.mesh, rules=sim.rules,
                        member_kernel=sim.member_kernel)


def _gather_snapshots(snaps) -> jnp.ndarray:
    """Stack dispatch snapshots into (B, d) with one gather per distinct
    source instead of one device slice per event. Entries are plain (d,)
    vectors (grouped by identity — e.g. the initial dispatches all share the
    version-0 vector) or ``(source (n, d), row)`` references into a previous
    flush's post-receive sequence."""
    groups: dict = {}
    order = []
    for pos, s in enumerate(snaps):
        src, row = s if isinstance(s, tuple) else (s, None)
        g = groups.get(id(src))
        if g is None:
            g = (src, [], [])
            groups[id(src)] = g
            order.append(g)
        g[1].append(row)
        g[2].append(pos)
    parts, positions = [], []
    for src, rows, poss in order:
        if rows[0] is None:
            parts.append(jnp.broadcast_to(src, (len(poss),) + src.shape))
        elif len(rows) == 1:
            parts.append(src[rows[0]][None])
        else:
            parts.append(src[jnp.asarray(np.asarray(rows, np.int32))])
        positions.extend(poss)
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if positions != list(range(len(snaps))):
        inv = np.empty(len(snaps), np.int32)
        inv[np.asarray(positions)] = np.arange(len(snaps), dtype=np.int32)
        out = out[jnp.asarray(inv)]
    return out


def _gather_snapshots_lanes(snaps) -> jnp.ndarray:
    """Lane-stacked ``_gather_snapshots``: entries are plain ``(S, d)``
    stacks (grouped by identity) or ``(source (S, n, d), row)`` references
    into a previous flush's post-receive sequence. Returns ``(S, B, d)``."""
    groups: dict = {}
    order = []
    for pos, s in enumerate(snaps):
        src, row = s if isinstance(s, tuple) else (s, None)
        g = groups.get(id(src))
        if g is None:
            g = (src, [], [])
            groups[id(src)] = g
            order.append(g)
        g[1].append(row)
        g[2].append(pos)
    parts, positions = [], []
    for src, rows, poss in order:
        if rows[0] is None:
            parts.append(jnp.broadcast_to(
                src[:, None, :], (src.shape[0], len(poss), src.shape[1])))
        elif len(rows) == 1:
            parts.append(src[:, rows[0]][:, None])
        else:
            parts.append(src[:, jnp.asarray(np.asarray(rows, np.int32))])
        positions.extend(poss)
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if positions != list(range(len(snaps))):
        inv = np.empty(len(snaps), np.int32)
        inv[np.asarray(positions)] = np.arange(len(snaps), dtype=np.int32)
        out = out[:, jnp.asarray(inv)]
    return out


def run_async(server_name: str, cfg: ModelConfig, init_params,
              client_datasets: List[ClientDataset], test_ds,
              sim: SimConfig, *, psa_cfg: Optional[psa_lib.PSAConfig] = None,
              calib_batch: Optional[dict] = None,
              server_kwargs: Optional[dict] = None,
              receive_hook: Optional[Callable] = None) -> SimResult:
    """Run one asynchronous algorithm to the virtual-time horizon."""
    engine = _resolve_engine(sim, cfg)
    batched = engine == "cohort"
    # One SimStreams bundle replaces the per-run RNG plumbing: the dispatch
    # stream (client sampling, owned by the scheduler), the latency jitter
    # stream, and the availability Bernoulli stream are decorrelated
    # sub-streams (see latency._subseed / scheduler.make_streams).
    streams = make_streams(sim)
    scheduler = make_scheduler(sim)
    if sim.checkpoint_dir and not (scheduler.stateless
                                   or scheduler.checkpoint_state):
        raise ValueError(
            f"scheduler {scheduler.name!r} keeps host-side state beyond its "
            f"RNG and does not implement the state_arrays checkpoint "
            f"round-trip; drop checkpoint_dir or use a checkpointable "
            f"scheduler")
    sketch_fn = None
    if server_name == "fedpsa":
        psa_cfg = psa_cfg or psa_lib.PSAConfig()
        assert calib_batch is not None
        sketch_fn = make_sketch_fn(cfg, calib_batch, psa_cfg)
    server = servers_lib.make_server(
        server_name, init_params, num_clients=sim.num_clients,
        psa_cfg=psa_cfg, sketch_fn=sketch_fn, mesh=sim.mesh, rules=sim.rules,
        **(server_kwargs or {}))
    align = getattr(server, "client_align", 0.0)
    digest_fn = (make_digest_fn(server.policy.spec.size)
                 if sim.record_trajectory else None)

    evaluate = _make_eval(cfg, test_ds, sim)
    result = SimResult(engine=engine)
    concurrency = max(1, int(round(sim.concurrency * sim.num_clients)))
    timeline = Timeline()
    data_sizes = _data_sizes(client_datasets)
    dispatcher = Dispatcher(sim, streams, scheduler, timeline, server,
                            result, batched=batched, data_sizes=data_sizes)

    t0 = next_eval0 = 0.0
    resumed = None
    if sim.checkpoint_dir and sim.resume:
        resumed = _ckpt_restore(sim, server, streams.rng, streams.latency,
                                streams.avail_rng, timeline, scheduler,
                                result, batched)
    if resumed is None:
        dispatcher.dispatch_many(np.zeros(concurrency))
    else:
        t0, next_eval0, dispatcher.seq = resumed

    ckpt = None
    if sim.checkpoint_dir and sim.checkpoint_every > 0:
        nxt = [(np.floor(t0 / sim.checkpoint_every) + 1)
               * sim.checkpoint_every]

        def ckpt(timeline_, t_, next_eval_):
            if t_ < nxt[0]:
                return
            _ckpt_save(sim, server, streams.rng, streams.latency,
                       streams.avail_rng, timeline_, scheduler, result, t_,
                       next_eval_, dispatcher.seq)
            while nxt[0] <= t_:
                nxt[0] += sim.checkpoint_every

    if batched:
        t = _drain_cohort(server, cfg, init_params, client_datasets, sim,
                          dispatcher.dispatch_many, timeline, evaluate,
                          result, data_sizes, align, psa_cfg, calib_batch,
                          receive_hook, digest_fn, t0=t0,
                          next_eval0=next_eval0, ckpt=ckpt)
    else:
        t = _drain_sequential(server, cfg, client_datasets, sim,
                              dispatcher.dispatch, timeline, evaluate,
                              result, data_sizes, align,
                              sketch_fn, receive_hook, digest_fn,
                              t0=t0, next_eval0=next_eval0, ckpt=ckpt)

    result.final_accuracy = evaluate(server.params)
    result.times.append(min(t, sim.horizon))
    result.accuracies.append(result.final_accuracy)
    result.versions = server.version
    result.server_log = server.log
    return result


def _drain_sequential(server, cfg, client_datasets, sim: SimConfig, dispatch,
                      timeline, evaluate, result: SimResult, data_sizes,
                      align, sketch_fn, receive_hook, digest_fn=None, *,
                      t0: float = 0.0, next_eval0: float = 0.0,
                      ckpt=None) -> float:
    """Legacy reference loop: one local_update per completion (oracle)."""
    next_eval = next_eval0
    t = t0
    while timeline and t < sim.horizon:
        if ckpt is not None:
            ckpt(timeline, t, next_eval)
        ev = timeline.pop()
        t = ev.t_done
        if t > sim.horizon:
            break
        while next_eval <= t:
            acc = evaluate(server.params)
            result.times.append(next_eval)
            result.accuracies.append(acc)
            next_eval += sim.eval_every
        if not ev.ok:
            result.dropped += 1
            dispatch(t)
            continue
        lr = sim.lr * (sim.lr_decay ** result.dispatches)
        delta, w_client = client_lib.local_update(
            ev.snapshot, cfg, client_datasets[ev.cid],
            epochs=sim.local_epochs, batch_size=sim.batch_size, lr=lr,
            seed=sim.seed * 100003 + result.dispatches, align=align)
        meta = {
            "tau": server.version - ev.version,
            "client_id": ev.cid,
            "data_size": float(data_sizes[ev.cid]),
        }
        if server.needs_sketch:
            meta["sketch"] = sketch_fn(w_client)
        if receive_hook is not None:
            receive_hook(server, w_client, delta, meta, t)
        server.receive(delta, w_client, meta)
        if digest_fn is not None:
            result.digests.append(
                digest_fn(server.flat_params[None, :])[0].tolist())
        result.dispatches += 1
        result.receive_log.append({"t": t, "tau": meta["tau"], "client": ev.cid})
        dispatch(t)
    return t


def _drain_cohort(server, cfg, init_params, client_datasets, sim: SimConfig,
                  dispatch_many, timeline, evaluate, result: SimResult,
                  data_sizes, align, psa_cfg, calib_batch, receive_hook,
                  digest_fn=None, *, t0: float = 0.0,
                  next_eval0: float = 0.0, ckpt=None) -> float:
    """Batched drain: train completion waves as single device calls.

    A wave is the maximal timeline prefix with ``t_done < t_first +
    latency_lo`` (capped at ``sim.max_cohort``). Any dispatch issued while
    the wave is being received completes no earlier than ``t_first +
    latency_lo`` — and at an equal timestamp sorts after the wave by ``seq``
    — so training the wave up front observes exactly the snapshots, learning
    rates, and seeds the sequential engine would have used.

    Each wave is a ``sim.wave`` span (``repro.common.obs``) holding its
    ``sim.assemble`` (timeline pops), ``sim.gather`` (snapshot stacking),
    ``sketch.enqueue`` and ``eval`` spans, besides those of the engine, the
    server and the dispatcher it calls.
    """
    spec = server.policy.spec
    engine = _make_cohort_engine(cfg, client_datasets, spec, init_params,
                                 sim, align=align)
    # prefetch only has a target on the streaming engine (the monolithic
    # slab is fully device-resident already)
    prefetch_store = (getattr(engine, "store", None) if sim.prefetch
                      else None)
    sketch_flat = None
    if server.needs_sketch:
        sketch_flat = make_sketch_fn_flat(cfg, calib_batch, psa_cfg, spec,
                                          engine.mesh, engine.cohort_axis)
    unflatten = tu.jit_unflatten(spec) if receive_hook is not None else None

    next_eval = next_eval0
    t = t0
    while timeline and t < sim.horizon:
        with obs.span("sim.wave"):
            if ckpt is not None:
                ckpt(timeline, t, next_eval)
            with obs.span("sim.assemble"):
                first = timeline.pop()
                if first.t_done > sim.horizon:
                    t = first.t_done   # mirror the sequential pop-then-break
                    break
                bound = first.t_done + sim.latency_lo
                wave: List[_Event] = [first]
                t_over = None
                while (timeline and timeline.head_t() < bound
                       and len(wave) < sim.max_cohort):
                    ev = timeline.pop()
                    if ev.t_done > sim.horizon:
                        # discarded, like the sequential break
                        t_over = ev.t_done
                        break
                    wave.append(ev)

            ok_events = [ev for ev in wave if ev.ok]
            deltas = w_stack = sketches = None
            if ok_events:
                d0 = result.dispatches
                with obs.span("sim.gather"):
                    snapshots = _gather_snapshots(
                        [ev.snapshot for ev in ok_events])
                cids = [ev.cid for ev in ok_events]
                lrs = [sim.lr * (sim.lr_decay ** (d0 + r))
                       for r in range(len(ok_events))]
                seeds = [sim.seed * 100003 + (d0 + r)
                         for r in range(len(ok_events))]
                deltas, w_stack = engine.cohort_update(snapshots, cids, lrs,
                                                       seeds)
                if sketch_flat is not None:
                    with obs.span("sketch.enqueue"):
                        sketches = sketch_flat(w_stack)
                result.cohorts += 1

            # Receives are deferred into ``pending`` and flushed as ONE batched
            # ingest (``receive_many``) — flushing early only when an eval
            # boundary needs the intermediate global model, or per-event when a
            # receive_hook must observe pre-receive server state. Replacement
            # dispatches happen inside the flush, each snapshotting the global
            # vector as of *its* event (``snaps`` rows), so RNG order and
            # snapshot contents match the sequential engine exactly.
            pending: List[_Event] = []
            next_row = 0

            def flush():
                nonlocal next_row
                if not pending:
                    return
                ok = [ev for ev in pending if ev.ok]
                r0, r1 = next_row, next_row + len(ok)
                # pre-flush vector, for leading dropouts
                cur = server.flat_params
                snaps = None
                upd = np.zeros((0,), bool)
                if ok:
                    if receive_hook is not None:
                        assert len(pending) == 1
                        ev = ok[0]
                        meta = {"tau": server.version - ev.version,
                                "client_id": ev.cid,
                                "data_size": float(data_sizes[ev.cid])}
                        if sketches is not None:
                            meta["sketch"] = sketches[r0]
                        receive_hook(server, unflatten(w_stack[r0]),
                                     unflatten(deltas[r0]), meta, ev.t_done)
                    upd, taus, snaps = server.receive_many(
                        deltas[r0:r1], w_stack[r0:r1],
                        [ev.cid for ev in ok],
                        [float(data_sizes[ev.cid]) for ev in ok],
                        [ev.version for ev in ok],
                        None if sketches is None else sketches[r0:r1])
                    if digest_fn is not None:
                        result.digests.extend(digest_fn(snaps).tolist())
                    for ev, tau in zip(ok, taus):
                        result.receive_log.append(
                            {"t": ev.t_done, "tau": tau, "client": ev.cid})
                    result.dispatches += len(ok)
                    next_row = r1
                vcur = server.version - int(np.sum(upd))  # version pre-flush
                oi = 0
                # replacement dispatches batched as ONE run insertion; each
                # snapshots the global vector as of *its* event (snaps rows)
                ts_, snaps_, vers_ = [], [], []
                for ev in pending:
                    if ev.ok:
                        cur = (snaps, oi)   # row reference, gathered lazily
                        vcur += int(upd[oi])
                        oi += 1
                    else:
                        result.dropped += 1
                    ts_.append(ev.t_done)
                    snaps_.append(cur)
                    vers_.append(vcur)
                dispatch_many(ts_, snaps_, vers_)
                pending.clear()

            for ev in wave:
                t = ev.t_done
                if next_eval <= t:
                    flush()
                    while next_eval <= t:
                        with obs.span("eval"):
                            acc = evaluate(server.params)
                        result.times.append(next_eval)
                        result.accuracies.append(acc)
                        next_eval += sim.eval_every
                pending.append(ev)
                if receive_hook is not None:
                    flush()
            flush()
            # the wave's replacements are inserted: the NEXT wave's member set
            # is determined, so overlap its materialization + upload with the
            # still-retiring device work (device dispatch is async)
            if (prefetch_store is not None and t_over is None
                    and t < sim.horizon):
                nxt = timeline.peek_wave_cids(sim.latency_lo, sim.max_cohort,
                                              sim.horizon)
                if nxt.size:
                    prefetch_store.prefetch(nxt)
            if t_over is not None:
                t = t_over
                break
    return t


# ---------------------------------------------------------------------------
# Fleet sweep engine: S experiment lanes as ONE batched simulation
# ---------------------------------------------------------------------------

@dataclass
class SweepConfig:
    """S experiment variants ("lanes") of one batched simulation.

    All lanes share one event timeline (``SimConfig.timeline_seed``, falling
    back to ``SimConfig.seed``): latency draws, client sampling, dropout,
    wave boundaries and version bookkeeping are identical across lanes, so
    the whole grid trains and ingests through lane-vmapped compiled calls.
    What may vary per lane:

    * ``model_seeds`` — per-lane model-init seeds (``init_params`` is used
      for every lane when None),
    * ``data_seeds`` — per-lane client batch-shuffle seeds (``SimConfig
      .seed`` for every lane when None),
    * ``policy_params`` — per-lane dicts of timeline-preserving policy
      hyperparameters (``federated.policies.PolicyParams`` field names:
      alpha, a, server_lr, beta, gamma, delta, eps, use_thermometer,
      dist_mode — the asyncfeded l2/cosine metric, "l2"/"cosine" accepted).

    Shape-determining parameters (buffer_size, queue_len, sketch_k,
    num_clients) and the client sketch program (use_sensitivity) are
    structural: lanes must share them (pass via psa_cfg/server_kwargs).
    """
    num_lanes: Optional[int] = None
    model_seeds: Optional[List[int]] = None
    data_seeds: Optional[List[int]] = None
    policy_params: Optional[List[Optional[dict]]] = None

    def resolve(self, base_seed: int):
        given = [x for x in (self.model_seeds, self.data_seeds,
                             self.policy_params) if x is not None]
        lens = {len(x) for x in given}
        if self.num_lanes is not None:
            lens.add(int(self.num_lanes))
        if len(lens) > 1:
            raise ValueError(
                f"inconsistent lane counts in SweepConfig: {sorted(lens)}")
        S = lens.pop() if lens else 1
        if S < 1:
            raise ValueError("a sweep needs at least one lane")
        data_seeds = (list(self.data_seeds) if self.data_seeds is not None
                      else [base_seed] * S)
        hypers = (list(self.policy_params)
                  if self.policy_params is not None else [None] * S)
        model_seeds = (list(self.model_seeds)
                       if self.model_seeds is not None else None)
        return S, model_seeds, data_seeds, hypers


@dataclass
class SweepResult:
    """A batched ``SimResult``: shared timeline counters + per-lane streams.

    ``lane_accuracies[s]`` is lane s's learning curve over the shared
    ``times`` grid; ``digests[s]`` its per-receive trajectory digest stream
    (when ``record_trajectory``). ``lane(s)`` views one lane as a plain
    ``SimResult`` for code that consumes single runs."""
    num_lanes: int = 1
    times: List[float] = field(default_factory=list)
    lane_accuracies: List[List[float]] = field(default_factory=list)
    final_accuracy: List[float] = field(default_factory=list)
    versions: int = 0
    dispatches: int = 0
    launched: int = 0
    dropped: int = 0
    cohorts: int = 0
    engine: str = "cohort"
    receive_log: List[dict] = field(default_factory=list)
    digests: List[List[List[float]]] = field(default_factory=list)

    def lane(self, s: int) -> SimResult:
        return SimResult(
            times=list(self.times), accuracies=list(self.lane_accuracies[s]),
            final_accuracy=self.final_accuracy[s], versions=self.versions,
            dispatches=self.dispatches, launched=self.launched,
            dropped=self.dropped, cohorts=self.cohorts, engine=self.engine,
            receive_log=list(self.receive_log),
            digests=[list(d) for d in self.digests[s]])

    @property
    def aulc(self) -> List[float]:
        return [self.lane(s).aulc for s in range(self.num_lanes)]

    def accuracy_mean_std(self):
        a = np.asarray(self.final_accuracy, np.float64)
        return float(a.mean()), float(a.std())


def run_sweep(server_name: str, cfg: ModelConfig, init_params,
              client_datasets: List[ClientDataset], test_ds,
              sim: SimConfig, sweep: SweepConfig, *,
              psa_cfg: Optional[psa_lib.PSAConfig] = None,
              calib_batch: Optional[dict] = None,
              server_kwargs: Optional[dict] = None) -> SweepResult:
    """Run S variants of one async algorithm as ONE batched simulation.

    One host event heap drives every lane (see ``SweepConfig``); per wave
    the cohort engine trains an ``(S, B, d)`` snapshot stack in one compiled
    call (``CohortEngine.sweep_update``) and the lane-stacked server ingests
    it with one vmapped scan (``servers.LanePolicyServer``), so the whole
    seed x hyperparameter grid pays the per-dispatch overhead once instead
    of S times. Lane s reproduces the standalone run with
    ``SimConfig(seed=data_seeds[s], timeline_seed=<shared>)``, that lane's
    init params, and its hyper overrides, within float tolerance
    (``tests/test_sweep.py`` pins this).
    """
    if server_name == "fedavg":
        raise ValueError("run_sweep batches the async policies; run the "
                         "synchronous fedavg per seed instead")
    if sim.mesh is not None:
        raise ValueError("run_sweep is single-device; drop SimConfig.mesh")
    if sim.checkpoint_dir:
        raise ValueError("checkpointing supports single runs, not sweeps")
    engine = _resolve_engine(sim, cfg)
    if engine != "cohort":
        raise ValueError(
            "run_sweep requires the batched cohort engine (engine='cohort' "
            "and a registered model family)")
    S, model_seeds, data_seeds, lane_hypers = sweep.resolve(sim.seed)
    if model_seeds is None:
        params_lanes = [init_params] * S
    else:
        params_lanes = [model_lib.init_params(jax.random.PRNGKey(int(s)), cfg)
                        for s in model_seeds]

    streams = make_streams(sim)
    scheduler = make_scheduler(sim)
    sketch_fn = None
    if server_name == "fedpsa":
        psa_cfg = psa_cfg or psa_lib.PSAConfig()
        assert calib_batch is not None
        sketch_fn = make_sketch_fn(cfg, calib_batch, psa_cfg)
    server = servers_lib.make_lane_server(
        server_name, params_lanes, lane_hypers, num_clients=sim.num_clients,
        psa_cfg=psa_cfg, sketch_fn=sketch_fn, **(server_kwargs or {}))
    align = server.client_align
    spec = server.policy.spec
    digest_fn = (make_digest_fn(spec.size) if sim.record_trajectory else None)

    evaluate = _make_eval_lanes(cfg, test_ds, sim, spec)
    result = SweepResult(num_lanes=S, engine="cohort",
                         lane_accuracies=[[] for _ in range(S)],
                         digests=[[] for _ in range(S)])
    concurrency = max(1, int(round(sim.concurrency * sim.num_clients)))
    timeline = Timeline()
    data_sizes = _data_sizes(client_datasets)

    # Same Dispatcher as run_async: batched=True snapshots the (S, d) lane
    # stack, and the RNG stream layout is identical, so a 1-lane sweep
    # replays the exact single-run event timeline.
    dispatcher = Dispatcher(sim, streams, scheduler, timeline, server,
                            result, batched=True, data_sizes=data_sizes)
    dispatcher.dispatch_many(np.zeros(concurrency))

    t = _drain_sweep(server, cfg, params_lanes, client_datasets, sim,
                     dispatcher.dispatch_many, timeline, evaluate, result,
                     data_sizes, align, psa_cfg, calib_batch, digest_fn,
                     data_seeds)

    final = evaluate(server.flat_params)
    result.final_accuracy = [float(a) for a in final]
    result.times.append(min(t, sim.horizon))
    for s in range(S):
        result.lane_accuracies[s].append(result.final_accuracy[s])
    result.versions = server.version
    return result


def _drain_sweep(server, cfg, params_lanes, client_datasets, sim: SimConfig,
                 dispatch_many, timeline, evaluate, result: SweepResult,
                 data_sizes, align, psa_cfg, calib_batch, digest_fn,
                 data_seeds) -> float:
    """The cohort drain, lane-stacked: identical wave selection and flush
    ordering to ``_drain_cohort`` (the timeline is lane-invariant), with
    every tensor growing a leading lane axis."""
    S = server.num_lanes
    spec = server.policy.spec
    engine = _make_cohort_engine(cfg, client_datasets, spec, params_lanes[0],
                                 sim, align=align)
    prefetch_store = (getattr(engine, "store", None) if sim.prefetch
                      else None)
    sketch_lanes = None
    if server.needs_sketch:
        sketch_lanes = make_sketch_fn_lanes(cfg, calib_batch, psa_cfg, spec)

    next_eval = 0.0
    t = 0.0
    while timeline and t < sim.horizon:
        first = timeline.pop()
        if first.t_done > sim.horizon:
            t = first.t_done
            break
        bound = first.t_done + sim.latency_lo
        wave: List[_Event] = [first]
        t_over = None
        while (timeline and timeline.head_t() < bound
               and len(wave) < sim.max_cohort):
            ev = timeline.pop()
            if ev.t_done > sim.horizon:
                t_over = ev.t_done
                break
            wave.append(ev)

        ok_events = [ev for ev in wave if ev.ok]
        deltas = w_stack = sketches = None
        if ok_events:
            d0 = result.dispatches
            snapshots = _gather_snapshots_lanes(
                [ev.snapshot for ev in ok_events])
            cids = [ev.cid for ev in ok_events]
            lrs = [sim.lr * (sim.lr_decay ** (d0 + r))
                   for r in range(len(ok_events))]
            seeds = np.asarray(
                [[int(ds) * 100003 + (d0 + r)
                  for r in range(len(ok_events))] for ds in data_seeds])
            deltas, w_stack = engine.sweep_update(snapshots, cids, lrs, seeds)
            if sketch_lanes is not None:
                sketches = sketch_lanes(w_stack)
            result.cohorts += 1

        pending: List[_Event] = []
        next_row = 0

        def flush():
            nonlocal next_row
            if not pending:
                return
            ok = [ev for ev in pending if ev.ok]
            r0, r1 = next_row, next_row + len(ok)
            cur = server.flat_params       # (S, d) pre-flush stack
            snaps = None
            upd = np.zeros((0,), bool)
            if ok:
                upd, taus, snaps = server.receive_many(
                    deltas[:, r0:r1], w_stack[:, r0:r1],
                    [ev.cid for ev in ok],
                    [float(data_sizes[ev.cid]) for ev in ok],
                    [ev.version for ev in ok],
                    None if sketches is None else sketches[:, r0:r1])
                if digest_fn is not None:
                    rows = np.asarray(snaps)           # (S, B, d) once
                    for s in range(S):
                        result.digests[s].extend(digest_fn(rows[s]).tolist())
                for ev, tau in zip(ok, taus):
                    result.receive_log.append(
                        {"t": ev.t_done, "tau": tau, "client": ev.cid})
                result.dispatches += len(ok)
                next_row = r1
            vcur = server.version - int(np.sum(upd))
            oi = 0
            ts_, snaps_, vers_ = [], [], []
            for ev in pending:
                if ev.ok:
                    cur = (snaps, oi)
                    vcur += int(upd[oi])
                    oi += 1
                else:
                    result.dropped += 1
                ts_.append(ev.t_done)
                snaps_.append(cur)
                vers_.append(vcur)
            dispatch_many(ts_, snaps_, vers_)
            pending.clear()

        for ev in wave:
            t = ev.t_done
            if next_eval <= t:
                flush()
                while next_eval <= t:
                    accs = evaluate(server.flat_params)
                    result.times.append(next_eval)
                    for s in range(S):
                        result.lane_accuracies[s].append(float(accs[s]))
                    next_eval += sim.eval_every
            pending.append(ev)
        flush()
        if prefetch_store is not None and t_over is None and t < sim.horizon:
            nxt = timeline.peek_wave_cids(sim.latency_lo, sim.max_cohort,
                                          sim.horizon)
            if nxt.size:
                prefetch_store.prefetch(nxt)
        if t_over is not None:
            t = t_over
            break
    return t


def run_fedavg(cfg: ModelConfig, init_params, client_datasets: List[ClientDataset],
               test_ds, sim: SimConfig, *, prox: float = 0.0) -> SimResult:
    """Synchronous FedAvg: per round sample 20% of clients, wait for the
    slowest, aggregate weighted by client data size. With the cohort engine
    the whole round trains as one device call and the global model stays a
    flat (d,) vector between rounds."""
    streams = make_streams(sim)
    latency = streams.latency
    avail, avail_rng = streams.avail, streams.avail_rng
    trace = streams.trace
    use_trace, use_avail = streams.use_trace, streams.use_avail
    # Round sampling draws from its own _subseed stream: the bare dispatch
    # RandomState(tseed) belongs to the async schedulers, and sharing it
    # here let the sync path perturb async reproducibility at equal seeds.
    choice_rng = np.random.RandomState(
        _subseed(streams.tseed, STREAM_SYNC_CHOICE))
    evaluate = _make_eval(cfg, test_ds, sim)
    engine = _resolve_engine(sim, cfg)
    batched = engine == "cohort"
    result = SimResult(engine=engine)
    m = max(1, int(round(sim.concurrency * sim.num_clients)))
    data_sizes = _data_sizes(client_datasets)
    if batched:
        spec = tu.FlatSpec(init_params)
        engine = _make_cohort_engine(cfg, client_datasets, spec, init_params,
                                     sim, prox=prox)
        flat = jnp.array(spec.flatten(init_params), copy=True)
        params = None
    else:
        params = init_params
    t = 0.0
    next_eval = 0.0
    rnd = 0
    while t < sim.horizon:
        while next_eval <= t:
            acc = evaluate(spec.unflatten(flat) if batched else params)
            result.times.append(next_eval)
            result.accuracies.append(acc)
            next_eval += sim.eval_every
        chosen = choice_rng.choice(sim.num_clients, size=m, replace=False)
        result.launched += len(chosen)
        round_time = float(latency.sample_for(chosen).max())
        if use_trace or use_avail:
            ok = (trace.on_at(chosen, np.full(m, t)) if use_trace
                  else avail_rng.rand(m) < avail[chosen])
            result.dropped += int(np.sum(~ok))
            active = [int(c) for c, o in zip(chosen, ok) if o]
        else:
            active = [int(c) for c in chosen]
        lr = sim.lr * (sim.lr_decay ** rnd)
        if active:
            sizes = np.asarray([data_sizes[c] for c in active], np.float32)
            w = jnp.asarray(sizes / np.sum(sizes))
            seeds = [sim.seed * 100003 + rnd * 51 + c for c in active]
            if batched:
                snapshots = jnp.broadcast_to(flat, (len(active), flat.shape[0]))
                deltas, _ = engine.cohort_update(snapshots, active,
                                                 [lr] * len(active), seeds)
                flat = flat + jnp.einsum("b,bd->d", w, deltas)
                result.cohorts += 1
            else:
                deltas = []
                for c, s in zip(active, seeds):
                    d, _ = client_lib.local_update(
                        params, cfg, client_datasets[c],
                        epochs=sim.local_epochs, batch_size=sim.batch_size,
                        lr=lr, seed=s, prox=prox)
                    deltas.append(d)
                params = tu.tree_add(params, tu.tree_weighted_sum(deltas, w))
        t += round_time
        rnd += 1
        result.dispatches += len(active)
    final_params = spec.unflatten(flat) if batched else params
    result.final_accuracy = evaluate(final_params)
    result.times.append(min(t, sim.horizon))
    result.accuracies.append(result.final_accuracy)
    result.versions = rnd
    return result


ALGORITHMS = ("fedavg", "fedasync", "fedbuff", "fedpsa", "ca2fl", "fedfa",
              "fedpac", "asyncfeded")


def run_algorithm(name: str, cfg: ModelConfig, init_params, client_datasets,
                  test_ds, sim: SimConfig, **kw) -> SimResult:
    if name == "fedavg":
        kw.pop("psa_cfg", None)
        kw.pop("calib_batch", None)
        return run_fedavg(cfg, init_params, client_datasets, test_ds, sim, **kw)
    return run_async(name, cfg, init_params, client_datasets, test_ds, sim, **kw)
