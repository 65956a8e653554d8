"""Server-side aggregation strategies — thin shims over the policy core.

Every async algorithm (fedasync, fedbuff, fedpsa, ca2fl, fedfa, fedpac,
asyncfeded; the synchronous fedavg runs round-based in the simulator) is a
pure jit-compiled ``policy.step`` in ``repro.federated.policies``.
``PolicyServer`` adapts that functional core to the legacy object interface
the simulator and benchmarks speak:

    receive(delta, client_params, meta) -> bool   # True if global updated
    receive_many(...)                             # batched ingest (one scan)
    params                                        # current global pytree
    flat_params                                   # current global (d,) vector
    version                                       # number of global updates

``meta`` carries tau (version gap), client_id, data_size and, for FedPSA,
the uploaded sensitivity sketch. One ``receive`` costs exactly one jitted
device call; ``receive_many`` ingests a whole completion wave by scanning
the policy's raw step — equivalent to B receives but with O(log B) device
calls. ``params`` unflattens the flat state vector lazily (cached per
version). The original unjitted classes live in ``repro.federated.legacy``
as the numerical reference.

``ShardedPolicyServer`` is the mesh-sharded drop-in: the same policy steps
run under ``shard_map`` with every ``(…, d)`` tensor of ``ServerState``
partitioned over the mesh's flat-parameter axis (see
``server_state_specs`` for the layout contract) and only scalar reductions
crossing shards via ``psum`` (``common.sharding.param_axis_sum``).

Policy keyword arguments flow through ``make_server``/``make_lane_server``
``**kw`` to the policy factory — e.g. ``metric="cosine"``/``"sketch"``
selects the asyncfeded distance-staleness variant (the traced l2/cosine
``dist_mode`` may instead vary per sweep lane via the lane hyper dicts; see
``core.psa.DISTANCE_METRICS``).
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import obs
from repro.common import sharding
from repro.common import tree as tu
from repro.core import psa as psa_lib
from repro.federated import policies as pol


_STEP_MANY_CACHE = {}
_SKETCH_REFRESH_CACHE = {}
_SHARDED_STEP_CACHE = {}
_SHARDED_MANY_CACHE = {}


def _scan_many(raw):
    """The ONE batched-ingest body both layouts compile: scan ``raw`` over
    a batch of arrivals ordered by completion time. ``arrs.tau`` carries
    each arrival's version-at-dispatch; the true staleness depends on
    updates applied by *earlier arrivals in this same batch*, so it is
    resolved inside the scan, which also emits the post-receive flat
    vector per arrival (what a re-dispatch at that instant snapshots)."""

    def many(state, arrs):
        def body(s, a):
            tau = s.version.astype(jnp.float32) - a.tau
            s, info = raw(s, a._replace(tau=tau))
            return s, (info, s.params)

        state, (infos, params_seq) = jax.lax.scan(body, state, arrs)
        return state, infos, params_seq

    return many


class PolicyServer:
    """Host-side adapter around one ``Policy``: owns the ``ServerState``,
    converts metas to ``Arrival``s, and renders ``StepInfo`` into the
    per-update log the benchmarks consume."""

    def __init__(self, policy: pol.Policy, params):
        self.policy = policy
        self.name = policy.name
        self.needs_sketch = policy.needs_sketch
        self.client_align = policy.client_align
        self.state = policy.init(params)
        self._step = policy.step
        self._step_many = None
        self.log: List[dict] = []
        self._version = 0
        self._tree_cache = None
        self._tree_cache_version = -1
        self._flat_cache = None
        self._flat_cache_version = -1
        self._unflatten = tu.jit_unflatten(policy.spec)

    # -- layout hooks (identity here; ShardedPolicyServer pads/strips) ------

    def _prep_vec(self, x):
        """Adapt one delta/client-params argument to the step's layout."""
        return x

    def _prep_stack(self, x):
        """Adapt a stacked (B, d) argument to the batched step's layout."""
        return x

    def _strip_stack(self, snaps):
        """Undo ``_prep_stack`` on the returned (B, d) snapshot rows."""
        return snaps

    @property
    def params(self):
        if self._tree_cache_version != self._version:
            self._tree_cache = self._unflatten(self.flat_params)
            self._tree_cache_version = self._version
        return self._tree_cache

    @property
    def flat_params(self):
        """Current global model as the flat (d,) vector — the dispatch
        snapshot the cohort engine trains from. Copied (cached per version):
        the live ``state.params`` buffer is donated to the next jitted step,
        so a reference held across ``receive`` would be a deleted array."""
        if self._flat_cache_version != self._version:
            self._flat_cache = jnp.copy(self.state.params)
            self._flat_cache_version = self._version
        return self._flat_cache

    @property
    def version(self) -> int:
        return self._version

    @property
    def psa(self) -> Optional[psa_lib.PSAState]:
        """Snapshot of the FedPSA sub-state (e.g. ``server.psa.global_sketch``).

        Copied: the live state's buffers are donated to the next jitted step,
        so a reference held across ``receive`` would be a deleted array."""
        if self.state.psa is None:
            return None
        return jax.tree_util.tree_map(jnp.copy, self.state.psa)

    def receive(self, delta, client_params, meta) -> bool:
        """Ingest one completion. ``delta``/``client_params`` may be pytrees
        (legacy path) or flat (d,) vectors (cohort path) — ``spec.flatten``
        inside the jitted step is the identity on an already-flat vector, so
        the two layouts just select different traced variants of the same
        policy step."""
        if self.needs_sketch and "sketch" not in meta:
            raise KeyError(
                f"{self.name} requires meta['sketch'] (behavioral sketch)")
        if self.state.cache is not None:
            cid = int(meta["client_id"])  # cache policies require a real id
            if not 0 <= cid < self.state.cache.data.shape[0]:
                raise ValueError(
                    f"client_id {cid} outside the server's num_clients="
                    f"{self.state.cache.data.shape[0]} cache")
        else:
            cid = int(meta.get("client_id", 0))
        arrival = pol.Arrival(
            update=self._prep_vec(delta),
            client_params=self._prep_vec(client_params),
            tau=jnp.float32(meta.get("tau", 0)),
            client_id=jnp.int32(cid),
            data_size=jnp.float32(meta.get("data_size", 1.0)),
            sketch=jnp.asarray(
                meta["sketch"], jnp.float32) if "sketch" in meta
            else jnp.zeros((self.policy.sketch_k,), jnp.float32),
        )
        self.state, info = self._step(self.state, arrival)
        updated = bool(info.updated)
        if updated:
            self._version += 1
            if self.policy.log_fn is not None:
                entry = self.policy.log_fn(info, meta)
                if entry is not None:
                    self.log.append(entry)
        return updated

    def _build_step_many(self):
        # keyed on the raw step — shared across every policy instance with
        # the same structure (hyper values live in the traced state), so
        # repeated runs AND hyperparameter grids reuse one compiled scan per
        # chunk size
        raw = self.policy.raw_step
        assert raw is not None, f"{self.name} has no raw_step for batched ingest"
        cached = _STEP_MANY_CACHE.get(raw)
        if cached is not None:
            return cached
        fn = jax.jit(_scan_many(raw), donate_argnums=(0,))
        _STEP_MANY_CACHE[raw] = fn
        return fn

    def receive_many(self, deltas, client_params, client_ids, data_sizes,
                     v_dispatch, sketches=None):
        """Batched ingest: apply B completions (stacked flat (B, d) arrays,
        ordered by completion time) with one scanned device call per
        power-of-two chunk instead of B separate ``receive`` calls.

        Exactly equivalent to B sequential ``receive``s: the scan threads the
        state through in order, staleness is resolved per-arrival inside the
        scan from ``v_dispatch`` (version at dispatch), and the returned
        ``snapshots[i]`` is the flat global vector *after* arrival i — what a
        completion-triggered re-dispatch at that instant must train from.
        Returns (updated (B,) bool, taus (B,) int list, snapshots (B, d)).
        """
        if self.needs_sketch and sketches is None:
            raise KeyError(f"{self.name} requires behavioral sketches")
        B = int(deltas.shape[0])
        ids = np.asarray(client_ids, np.int64)
        if self.state.cache is not None:
            n = self.state.cache.data.shape[0]
            if ids.size and (ids.min() < 0 or ids.max() >= n):
                raise ValueError(
                    f"client_id outside the server's num_clients={n} cache")
        if self.policy.raw_step is None:
            # policy registered without a raw step (pre-batching style):
            # degrade to per-event ingest instead of failing
            return self._receive_many_fallback(deltas, client_params, ids,
                                               data_sizes, v_dispatch,
                                               sketches)
        if self._step_many is None:
            self._step_many = self._build_step_many()
        if sketches is None:
            sketches = jnp.zeros((B, self.policy.sketch_k), jnp.float32)
        deltas = self._prep_stack(deltas)
        client_params = self._prep_stack(client_params)
        state = self.state
        infos_parts, snap_parts = [], []
        off = 0
        while off < B:
            # largest power-of-two chunk so the jit cache stays O(log B)
            chunk = 1 << int(np.log2(B - off))
            sl = slice(off, off + chunk)
            with obs.span("ingest.enqueue"):
                arrs = pol.Arrival(
                    update=deltas[sl], client_params=client_params[sl],
                    tau=jnp.asarray(v_dispatch[sl], jnp.float32),
                    client_id=jnp.asarray(ids[sl], jnp.int32),
                    data_size=jnp.asarray(data_sizes[sl], jnp.float32),
                    sketch=sketches[sl])
                state, infos, snaps = self._step_many(state, arrs)
            # the host waits for each chunk before it enqueues the next, so
            # that the device has freed a chunk's inputs before the next
            # chunk's are made: enqueueing every chunk first holds them all
            # at once and raises the peak memory
            with obs.span("ingest.wait"):
                jax.block_until_ready(infos)
            with obs.span("ingest.readback"):
                if self.policy.log_fn is None:
                    # only the update flags cross to the host (one copy,
                    # not six)
                    infos = infos._replace(updated=np.asarray(infos.updated))
                else:
                    infos = jax.tree_util.tree_map(np.asarray, infos)
            obs.record("ingest.chunk", arrivals=chunk,
                       aggregations=int(np.sum(infos.updated)))
            infos_parts.append(infos)
            snap_parts.append(snaps)
            off += chunk
        self.state = state
        updated = np.concatenate([p.updated.reshape(-1) for p in infos_parts])
        snapshots = (snap_parts[0] if len(snap_parts) == 1
                     else jnp.concatenate(snap_parts))
        with obs.span("ingest.log"):
            taus: List[int] = []
            v = self._version
            row = 0
            for part in infos_parts:
                for i in range(part.updated.shape[0]):
                    tau = v - int(v_dispatch[row])
                    taus.append(tau)
                    if part.updated[i]:
                        v += 1
                        if self.policy.log_fn is not None:
                            info_row = pol.StepInfo(*[np.asarray(f)[i]
                                                      for f in part])
                            meta = {"tau": tau, "client_id": int(ids[row]),
                                    "data_size": float(data_sizes[row])}
                            entry = self.policy.log_fn(info_row, meta)
                            if entry is not None:
                                self.log.append(entry)
                    row += 1
            self._version = v
        return updated, taus, self._strip_stack(snapshots)

    def _receive_many_fallback(self, deltas, client_params, ids, data_sizes,
                               v_dispatch, sketches):
        """Per-event equivalent of ``receive_many`` for policies with no
        ``raw_step`` — B ``receive`` calls plus per-row snapshot copies."""
        B = int(deltas.shape[0])
        updated = np.zeros((B,), bool)
        taus: List[int] = []
        rows = []
        for i in range(B):
            tau = self._version - int(v_dispatch[i])
            taus.append(tau)
            meta = {"tau": tau, "client_id": int(ids[i]),
                    "data_size": float(data_sizes[i])}
            if sketches is not None:
                meta["sketch"] = sketches[i]
            updated[i] = self.receive(deltas[i], client_params[i], meta)
            rows.append(self.flat_params)
        return updated, taus, jnp.stack(rows)


# ---------------------------------------------------------------------------
# Mesh-sharded execution layer
# ---------------------------------------------------------------------------

def server_state_specs(state: pol.ServerState, axis: str) -> pol.ServerState:
    """The sharded-layout contract, as a ``ServerState`` of PartitionSpecs.

    Exactly the tensors whose TRAILING axis is the flat parameter axis shard
    over the mesh: ``params`` (d,), ``ring.data`` (L, d), ``psa.buffer``
    (L_s, d), ``cache.data`` (C, d) and ``cache.total`` (d,). Everything
    else — versions, fill counts, kappas, the thermometer queue, sketches,
    cache validity — is small and replicated, so all cross-shard traffic is
    the scalar psums in ``param_axis_sum`` (plus FedPSA's all_gather on its
    sketch-refresh branch). A new policy opts in by storing its d-sized
    state in these fields (or extending this template alongside them)."""
    rep = P()
    row = P(axis)
    mat = P(None, axis)
    ring = None if state.ring is None else pol.RingState(data=mat, count=rep)
    cache = None if state.cache is None else pol.CacheState(
        data=mat, valid=rep, total=row)
    psa = None
    if state.psa is not None:
        psa = psa_lib.PSAState(
            buffer=mat, kappas=rep, count=rep,
            thermo=jax.tree_util.tree_map(lambda _: rep, state.psa.thermo),
            global_sketch=rep)
    hyper = (None if state.hyper is None else
             jax.tree_util.tree_map(lambda _: rep, state.hyper))
    return pol.ServerState(params=row, version=rep, ring=ring, psa=psa,
                           cache=cache, hyper=hyper)


def _arrival_specs(axis: str, batched: bool) -> pol.Arrival:
    vec = P(None, axis) if batched else P(axis)
    rep = P()
    return pol.Arrival(update=vec, client_params=vec, tau=rep, client_id=rep,
                       data_size=rep, sketch=rep)


_INFO_SPECS = pol.StepInfo(updated=P(), weights=P(), kappas=P(), temp=P(),
                           temp_valid=P(), mix=P())


def _pad_last(x: jnp.ndarray, d_pad: int) -> jnp.ndarray:
    """Zero-pad the trailing (flat parameter) axis up to the divisible
    width. The pad region is all-zero in every d-sized input, so it stays
    identically zero through every policy's elementwise update rules and
    contributes nothing to the psum'd reductions."""
    pad = d_pad - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


class ShardedPolicyServer(PolicyServer):
    """``PolicyServer`` with ``ServerState`` laid out over a one-axis mesh.

    The flat parameter axis is zero-padded to a device-count multiple and
    partitioned per ``server_state_specs``; the policy's *raw* step runs
    under ``shard_map`` (traced inside ``common.sharding.param_axis`` so
    its d-contractions psum), which makes the per-shard program the same
    elementwise/ring arithmetic as the single-device step — including the
    per-shard Pallas ``buffer_agg`` path on TPU. Host-facing results
    (``flat_params``, ``receive_many`` snapshots) strip the padding, so the
    simulator and cohort engine are layout-agnostic."""

    def __init__(self, policy: pol.Policy, params, mesh: Mesh,
                 rules: Optional[sharding.LogicalRules] = None):
        rules = rules or sharding.FEDERATED_RULES
        axis = rules.mesh_axes(("param_shard",))[0]
        if axis is None or axis not in mesh.axis_names:
            raise ValueError(
                f"rules must map 'param_shard' onto a mesh axis of "
                f"{mesh.axis_names}, got {axis!r}")
        self.mesh = mesh
        self.axis = axis
        self._d = policy.spec.size
        n = mesh.shape[axis]
        self._d_pad = -(-self._d // n) * n
        super().__init__(policy, params)
        self._specs = server_state_specs(self.state, axis)
        self.state = self._shard_state(self.state)
        self._step = self._build_step()

    # -- layout ------------------------------------------------------------

    def _shard_state(self, state: pol.ServerState) -> pol.ServerState:
        padded = jax.tree_util.tree_map(
            lambda x, s: _pad_last(x, self._d_pad)
            if (len(s) and s[-1] == self.axis) else x,
            state, self._specs)
        put = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self._specs,
            is_leaf=lambda s: isinstance(s, P))
        return jax.device_put(padded, put)

    def _prep_vec(self, x):
        # flatten is the identity reshape on an already-flat vector
        return _pad_last(self.policy.spec.flatten(x), self._d_pad)

    def _prep_stack(self, x):
        return _pad_last(jnp.asarray(x), self._d_pad)

    def _strip_stack(self, snaps):
        return snaps[:, :self._d] if snaps.shape[-1] != self._d else snaps

    @property
    def flat_params(self):
        """Current global model as the *unpadded* (d,) vector (the slice
        allocates a fresh buffer, so donation of the live state is safe)."""
        if self._flat_cache_version != self._version:
            # copy: when d == d_pad the slice can alias the live state
            # buffer, which the next donating step would invalidate
            self._flat_cache = jnp.copy(self.state.params[:self._d])
            self._flat_cache_version = self._version
        return self._flat_cache

    # -- compiled steps ----------------------------------------------------

    def _build_step(self):
        raw = self.policy.raw_step
        assert raw is not None, \
            f"{self.name} has no raw_step; cannot run sharded"
        key = (raw, self.mesh, self.axis)
        cached = _SHARDED_STEP_CACHE.get(key)
        if cached is not None:
            return cached
        axis = self.axis

        def body(state, arr):
            with sharding.param_axis(axis):
                return raw(state, arr)

        fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(self._specs, _arrival_specs(axis, batched=False)),
            out_specs=(self._specs, _INFO_SPECS), check_vma=False),
            donate_argnums=(0,))
        _SHARDED_STEP_CACHE[key] = fn
        return fn

    def _build_step_many(self):
        raw = self.policy.raw_step
        assert raw is not None, \
            f"{self.name} has no raw_step; cannot run sharded"
        key = (raw, self.mesh, self.axis)
        cached = _SHARDED_MANY_CACHE.get(key)
        if cached is not None:
            return cached
        axis = self.axis
        scan_many = _scan_many(raw)

        def many(state, arrs):
            # the context wraps the TRACE of the shared scan body, so its
            # d-contractions psum exactly as in the per-arrival step
            with sharding.param_axis(axis):
                return scan_many(state, arrs)

        fn = jax.jit(jax.shard_map(
            many, mesh=self.mesh,
            in_specs=(self._specs, _arrival_specs(axis, batched=True)),
            out_specs=(self._specs, _INFO_SPECS, P(None, axis)),
            check_vma=False), donate_argnums=(0,))
        _SHARDED_MANY_CACHE[key] = fn
        return fn


# ---------------------------------------------------------------------------
# Lane-stacked execution layer (the sweep engine's server half)
# ---------------------------------------------------------------------------

_LANE_MANY_CACHE = {}


class LanePolicyServer:
    """S experiment lanes of one policy as ONE stacked server.

    ``ServerState`` is stacked with a leading lane axis — per-lane global
    vectors, ring buffers, PSA state AND per-lane ``PolicyParams`` hyper
    leaves — and batched ingest runs ``jax.vmap`` of the same
    ``_scan_many(raw_step)`` body the single-run server scans, so one
    compiled program serves the whole hyperparameter/seed grid. The event
    TIMELINE (completion order, client ids, version bookkeeping, data
    sizes) is shared across lanes by construction: every policy's
    update/flush decision depends only on arrival counts, never on
    parameter values, so the ``updated`` flags are lane-invariant (asserted
    at ingest).

    Host-facing surface mirrors ``PolicyServer`` where it can: ``version``
    (shared), ``flat_params`` — now ``(S, d)`` — and ``receive_many`` over
    ``(S, B, d)`` stacks. Per-update host logs are not rendered (sweeps
    consume digest streams and metrics instead).
    """

    def __init__(self, policy: pol.Policy, params_per_lane,
                 hypers: List[pol.PolicyParams]):
        assert len(params_per_lane) == len(hypers) and len(hypers) >= 1
        self.policy = policy
        self.name = policy.name
        self.needs_sketch = policy.needs_sketch
        self.client_align = policy.client_align
        self.num_lanes = len(hypers)
        states = [policy.init(p, h) for p, h in zip(params_per_lane, hypers)]
        self.state = jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
        self._step_many = None
        self.log: List[dict] = []
        self._version = 0
        self._flat_cache = None
        self._flat_cache_version = -1

    @property
    def version(self) -> int:
        return self._version

    @property
    def flat_params(self) -> jnp.ndarray:
        """(S, d) stack of the lanes' current global vectors (copied: the
        live buffers are donated to the next jitted step)."""
        if self._flat_cache_version != self._version:
            self._flat_cache = jnp.copy(self.state.params)
            self._flat_cache_version = self._version
        return self._flat_cache

    def _build_step_many(self):
        raw = self.policy.raw_step
        assert raw is not None, \
            f"{self.name} has no raw_step; cannot run lane-stacked"
        cached = _LANE_MANY_CACHE.get(raw)
        if cached is not None:
            return cached
        scan_many = _scan_many(raw)
        arr_axes = pol.Arrival(update=0, client_params=0, tau=None,
                               client_id=None, data_size=None, sketch=0)
        fn = jax.jit(jax.vmap(scan_many, in_axes=(0, arr_axes)),
                     donate_argnums=(0,))
        _LANE_MANY_CACHE[raw] = fn
        return fn

    def receive_many(self, deltas, client_params, client_ids, data_sizes,
                     v_dispatch, sketches=None):
        """Batched lane ingest: apply B completions to every lane at once.

        ``deltas``/``client_params`` are ``(S, B, d)`` stacks (lane-major);
        the scalar arrival fields are shared across lanes. Returns
        ``(updated (B,) bool, taus (B,) ints, snapshots (S, B, d))`` — the
        same contract as ``PolicyServer.receive_many`` with a lane axis on
        the tensors.
        """
        if self.needs_sketch and sketches is None:
            raise KeyError(f"{self.name} requires behavioral sketches")
        S, B = int(deltas.shape[0]), int(deltas.shape[1])
        assert S == self.num_lanes, (S, self.num_lanes)
        ids = np.asarray(client_ids, np.int64)
        if self.state.cache is not None:
            n = self.state.cache.data.shape[1]
            if ids.size and (ids.min() < 0 or ids.max() >= n):
                raise ValueError(
                    f"client_id outside the server's num_clients={n} cache")
        if self._step_many is None:
            self._step_many = self._build_step_many()
        if sketches is None:
            sketches = jnp.zeros((S, B, self.policy.sketch_k), jnp.float32)
        state = self.state
        upd_parts, snap_parts = [], []
        off = 0
        while off < B:
            # largest power-of-two chunk, as in PolicyServer.receive_many
            chunk = 1 << int(np.log2(B - off))
            sl = slice(off, off + chunk)
            arrs = pol.Arrival(
                update=deltas[:, sl], client_params=client_params[:, sl],
                tau=jnp.asarray(v_dispatch[sl], jnp.float32),
                client_id=jnp.asarray(ids[sl], jnp.int32),
                data_size=jnp.asarray(data_sizes[sl], jnp.float32),
                sketch=sketches[:, sl])
            state, infos, snaps = self._step_many(state, arrs)
            upd_parts.append(np.asarray(infos.updated))   # (S, chunk) bool
            snap_parts.append(snaps)
            off += chunk
        self.state = state
        upd_lanes = np.concatenate(upd_parts, axis=1)
        # the lane contract: update decisions are count-driven, never
        # value-driven, so they cannot diverge across lanes
        assert bool(np.all(upd_lanes == upd_lanes[:1])), \
            "policy update decisions diverged across sweep lanes"
        updated = upd_lanes[0]
        snapshots = (snap_parts[0] if len(snap_parts) == 1
                     else jnp.concatenate(snap_parts, axis=1))
        taus: List[int] = []
        v = self._version
        for i in range(B):
            taus.append(v - int(v_dispatch[i]))
            v += int(updated[i])
        self._version = v
        return updated, taus, snapshots


def make_lane_server(name: str, params_per_lane, lane_hypers, *,
                     num_clients: int = 50,
                     psa_cfg: Optional[psa_lib.PSAConfig] = None,
                     sketch_fn: Optional[Callable] = None,
                     **kw) -> LanePolicyServer:
    """Build the lane-stacked server for one algorithm.

    ``params_per_lane`` is a list of S parameter pytrees (identical
    layouts); ``lane_hypers`` a list of S dicts of per-lane hyperparameter
    overrides (``PolicyParams`` field names — e.g. ``{"alpha": 0.3}`` or
    ``{"gamma": 0.1, "use_thermometer": False}``) merged over the policy's
    factory defaults. Structural kwargs (buffer_size, psa_cfg shapes, ...)
    are shared by all lanes — ``make_hyper`` rejects them per lane."""
    spec = tu.FlatSpec(params_per_lane[0])
    sketch_refresh = None
    if name == "fedpsa":
        assert psa_cfg is not None and sketch_fn is not None
        key = (id(sketch_fn), spec)
        sketch_refresh = _SKETCH_REFRESH_CACHE.get(key)
        if sketch_refresh is None:
            sketch_refresh = lambda vec: sketch_fn(spec.unflatten(vec))
            sketch_refresh._sketch_fn = sketch_fn   # keep the id() key alive
            _SKETCH_REFRESH_CACHE[key] = sketch_refresh
    policy = pol.make_policy(name, spec, num_clients=num_clients,
                             psa_cfg=psa_cfg, sketch_refresh=sketch_refresh,
                             **kw)
    defaults = dict(policy.hyper_defaults)
    hypers = []
    for over in lane_hypers:
        merged = dict(defaults)
        merged.update(over or {})
        hypers.append(pol.make_hyper(**merged))
    return LanePolicyServer(policy, params_per_lane, hypers)


def make_server(name: str, params, *, num_clients: int = 50,
                psa_cfg: Optional[psa_lib.PSAConfig] = None,
                sketch_fn: Optional[Callable] = None,
                mesh: Optional[Mesh] = None,
                rules: Optional[sharding.LogicalRules] = None,
                **kw) -> PolicyServer:
    """Build the policy-backed server for one algorithm.

    ``sketch_fn`` (fedpsa) maps a params *pytree* to its (k,) sketch; the
    policy core re-expresses it over the flat layout so the global-sketch
    refresh fuses into the jitted step. With ``mesh`` the server state is
    laid out over the mesh's flat-parameter axis (``ShardedPolicyServer``);
    ``rules`` (default ``common.sharding.FEDERATED_RULES``) names the mesh
    axis via the ``param_shard`` logical axis."""
    spec = tu.FlatSpec(params)
    sketch_refresh = None
    if name == "fedpsa":
        assert psa_cfg is not None and sketch_fn is not None
        key = (id(sketch_fn), spec)
        sketch_refresh = _SKETCH_REFRESH_CACHE.get(key)
        if sketch_refresh is None:
            sketch_refresh = lambda vec: sketch_fn(spec.unflatten(vec))
            sketch_refresh._sketch_fn = sketch_fn   # keep the id() key alive
            _SKETCH_REFRESH_CACHE[key] = sketch_refresh
    policy = pol.make_policy(name, spec, num_clients=num_clients,
                             psa_cfg=psa_cfg, sketch_refresh=sketch_refresh,
                             **kw)
    if mesh is not None:
        return ShardedPolicyServer(policy, params, mesh, rules)
    return PolicyServer(policy, params)
