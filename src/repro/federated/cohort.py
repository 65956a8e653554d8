"""Device-resident cohort client engine: vmapped local training.

The legacy client path (``client.local_update``) runs E epochs as a python
loop of per-batch jit calls on pytrees — every simulated dispatch pays
O(epochs * batches) device-call overhead plus a pytree snapshot. This module
replaces it with ONE compiled call per *cohort*: all clients whose
completions drain together train simultaneously via ``vmap`` over the cohort
axis and a ``lax.fori_loop`` over their local SGD steps, operating directly on
the flat ``(d,)`` parameter layout from ``common.tree.FlatSpec`` (no pytree
unflatten on the host — ``spec.unflatten`` happens inside the traced loss).

Data lives on device once, as a padded ``(C, n_max, ...)`` slab
(``data.loader.StackedClients`` — float features for image families,
``(C, n_max, seq)`` int32 token/label arrays for LM families); batch
schedules come from the same ``epoch_batch_indices`` stream the legacy
iterator uses, so the engine reproduces the per-client loop's arithmetic to
float tolerance — ragged client sizes are handled by masking batch tails
inside the loss, and padded steps / padded cohort rows are exact no-ops.

The member loss is model-agnostic: it comes from the family registry
(``models.registry.get_family(cfg).client_loss`` with the mask folded in by
``masked_batch``), so ANY registered family — the paper's cnn/mlp, the
dense/ssm/moe/hybrid LM families via ``model_lib.loss_fn`` (remat honored
per ``ModelConfig``), or a user-registered one — compiles into the same
vmap x loop program.

FedProx (``prox``) and FedPAC (``align``) fold in as static config: the
proximal/alignment pulls are plain vector arithmetic on the flat layout
(the classifier head becomes a precomputed 0/1 mask over flat offsets).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import obs
from repro.common import sharding
from repro.common import tree as tu
from repro.common.sharding import SINGLE_DEVICE_RULES
from repro.data.loader import StackedClients, epoch_batch_indices
from repro.federated.client import _head
from repro.models import member_math
from repro.models import registry
from repro.models.config import ModelConfig


_RUN_CACHE = {}


def bucket_size(B: int, data_kind: str = "tokens") -> int:
    """Pad a wave of B members up to the family's bucket grid. Padded rows
    are masked no-ops but still execute their local steps, so the grid
    trades padded compute against compiled-program count:

    ``image`` — multiples of 4 (max_cohort/4 programs, <= 3 wasted rows):
    the cnn/mlp programs compile in milliseconds, so a dense grid is free.

    ``tokens`` — {4, 6, 8, 12, 16, 24, 32, ...} (powers of two and 1.5x
    powers of two; worst-case 1.5x padded compute, O(log max_cohort)
    programs): transformer-family programs compile in *seconds* each, so a
    dense grid would stall mid-run on every fresh wave size."""
    if data_kind == "image":
        return -(-B // 4) * 4
    if B <= 4:
        return 4
    p = 1 << (B - 1).bit_length()          # next power of two >= B
    return 3 * p // 4 if 3 * p // 4 >= B else p


def wave_axis(mesh, axis: Optional[str], Bp: int) -> Optional[str]:
    """The mesh axis a wave of ``Bp`` bucketed members splits its cohort
    axis over: ``axis`` when ``Bp`` divides that axis, else None — the wave
    then runs on the mesh replicated (exact either way). The engine and the
    batched client sketch both place a wave by this rule."""
    return axis if axis is not None and Bp % mesh.shape[axis] == 0 else None


class CohortEngine:
    """One compiled local-training step for a whole cohort.

    Built once per (model, stacked data, epochs, batch_size, prox, align);
    ``cohort_update`` then costs one device call per cohort. Cohort sizes
    are bucketed to the ``bucket_size`` grid and the schedule arrays keep
    the engine-wide ``num_steps`` frame, so the jit cache holds one program
    per row bucket, not one per cohort shape. The local-SGD loop's trip
    count is a traced argument: each wave stops at its longest member's
    last real step, and one compiled program serves every trip count.
    """

    def __init__(self, cfg: ModelConfig, stacked: StackedClients,
                 spec: tu.FlatSpec, template_params, *,
                 local_epochs: int = 5, batch_size: int = 64,
                 prox: float = 0.0, align: float = 0.0,
                 mesh=None, rules: Optional[sharding.LogicalRules] = None,
                 member_kernel: str = "vmap"):
        # any registered family compiles; get_family raises (naming the
        # registered set) for families the registry does not know
        fam = registry.get_family(cfg)
        self._data_kind = fam.data_kind
        self.cfg = cfg
        self.spec = spec
        self.local_epochs = int(local_epochs)
        self.batch_size = int(batch_size)
        self.prox = float(prox)
        self.align = float(align)
        if member_kernel not in member_math.MODES:
            raise ValueError(f"member_kernel must be one of "
                             f"{member_math.MODES}, got {member_kernel!r}")
        self.member_kernel = member_kernel
        self.sizes = np.asarray(stacked.sizes, np.int64)
        self.x = jnp.asarray(stacked.x)
        self.y = jnp.asarray(stacked.y)
        # With a mesh, a wave trains data-parallel: the cohort (client) axis
        # of every per-member input shards over the ``cohort`` logical axis
        # and the data slab replicates; vmap members are independent, so the
        # numerics are identical to the single-device call.
        self.mesh = mesh
        self.cohort_axis = None
        if mesh is not None:
            rules = rules or sharding.FEDERATED_RULES
            ax = rules.mesh_axes(("cohort",))[0]
            if ax is not None and ax in mesh.axis_names:
                self.cohort_axis = ax
            rep = NamedSharding(mesh, P())
            self.x = jax.device_put(self.x, rep)
            self.y = jax.device_put(self.y, rep)
        # Per-client steps/epoch under the drop-last rule; a wave's loop runs
        # its longest member's count and masks the shorter members' tails (a
        # masked step is an exact no-op).
        bs_c = np.minimum(self.batch_size, self.sizes)
        self.steps_per_client = (self.local_epochs * (self.sizes // bs_c)).astype(int)
        self.num_steps = int(self.steps_per_client.max())
        self.bs_pad = int(bs_c.max())
        # Compiled step shared across engine instances (a fresh engine per
        # run would otherwise retrace; mirrors client._STEP_CACHE). The key
        # pins everything _build closes over: the model (which fixes the
        # flat layout), the static loss variant, and the registry entry —
        # so register_family(..., override=True) invalidates the program.
        key = (cfg, spec, self.prox, self.align, fam, member_kernel)
        if key not in _RUN_CACHE:
            _RUN_CACHE[key] = self._build(cfg, spec, self.prox, self.align,
                                          fam, member_kernel)
        self._run, self._run_lanes = _RUN_CACHE[key]

    # -- compiled core ------------------------------------------------------

    @staticmethod
    def _local_sgd(cfg, spec, prox, align, fam, member_kernel="vmap"):
        """One member's local training on its own rows ``xs``/``ys``: the
        program both engines vmap over their cohort axis."""
        def member(xs, ys, p0_flat, idx, valid, counts, lr_steps, n_steps):
          # member-math routing is a trace-time switch: "grouped" makes the
          # vmap over members collapse every dense layer into one Pallas
          # grouped-GEMM launch (models.member_math); "vmap" keeps the exact
          # per-member dot_general HLO the golden digests pin.
          with member_math.routing(member_kernel):
            # The loop carries the params *pytree*: unflatten/flatten happen
            # once at the boundary, not (with their grad-transpose scatters)
            # inside every local step — the per-step program stays the same
            # op sequence the legacy per-batch jit ran.
            anchor = spec.unflatten(p0_flat)

            def loss(p, xb, yb, vm, cnt):
                base = fam.client_loss(p, fam.masked_batch(xb, yb, vm, cnt),
                                       cfg, SINGLE_DEVICE_RULES)
                if prox > 0.0:
                    base = base + 0.5 * prox * tu.tree_sq_norm(
                        tu.tree_sub(p, anchor))
                if align > 0.0:
                    base = base + 0.5 * align * tu.tree_sq_norm(
                        tu.tree_sub(_head(p), _head(anchor)))
                return base

            grad = jax.grad(loss)

            # vm (f32 tail mask), cnt (= max(sum(vm), 1)) and lr_t (member lr,
            # 0 on padded steps) are host-precomputed so the compiled step
            # carries no mask bookkeeping; a padded step has finite g (safe
            # denominator) and lr_t = 0 — an exact no-op.
            def body(t, p):
                bi = idx[t]
                g = grad(p, xs[bi], ys[bi], valid[t], counts[t])
                return jax.tree_util.tree_map(
                    lambda a, b: a - lr_steps[t] * b, p, g)

            # n_steps is traced and every vmap leaves it unbatched, so the
            # loop stays one while loop with a scalar predicate, and each
            # trip count runs on the same compiled program.
            return spec.flatten(jax.lax.fori_loop(0, n_steps, body, anchor))

        return member

    @staticmethod
    def _build(cfg, spec, prox, align, fam, member_kernel="vmap"):
        local_sgd = CohortEngine._local_sgd(cfg, spec, prox, align, fam,
                                            member_kernel)

        def member(x_all, y_all, p0_flat, cid, idx, valid, counts, lr_steps,
                   n_steps):
            # x_all[cid]: this member's (n_max, ...) rows of the slab
            return local_sgd(x_all[cid], y_all[cid], p0_flat, idx, valid,
                             counts, lr_steps, n_steps)

        over_members = jax.vmap(
            member, in_axes=(None, None, 0, 0, 0, 0, 0, 0, None))

        @jax.jit
        def run(x_all, y_all, params_stack, cids, idx, valid, counts,
                lr_steps, n_steps):
            w = over_members(x_all, y_all, params_stack, cids, idx, valid,
                             counts, lr_steps, n_steps)
            return w - params_stack, w

        # The sweep engine's variant: one more vmap over a leading lane
        # axis. Lanes share the data slab, the member (client) assignment,
        # the validity masks/counts (schedule shapes depend only on client
        # sizes), the lr schedule and the trip count — all lane-invariant
        # because the event timeline is shared; the dispatch snapshots and
        # the batch-index permutations are per-lane (per-lane weights /
        # shuffle seeds).
        @jax.jit
        def run_lanes(x_all, y_all, params_stack, cids, idx, valid, counts,
                      lr_steps, n_steps):
            w = jax.vmap(over_members,
                         in_axes=(None, None, 0, None, 0, None, None, None,
                                  None))(
                x_all, y_all, params_stack, cids, idx, valid, counts,
                lr_steps, n_steps)
            return w - params_stack, w

        return run, run_lanes

    # -- host driver --------------------------------------------------------

    def _schedules(self, cids: np.ndarray, seeds: np.ndarray):
        """Batch schedules for a cohort, padded to the engine's fixed
        (num_steps, bs_pad) frame. Same RandomState stream as the legacy
        ``ClientDataset.epochs`` iterator. Returns (idx, valid f32 masks,
        counts = per-step valid totals clamped to >= 1, nvalid per-step raw
        totals for lr gating).

        The frame stays at ``num_steps`` whatever the wave, so each row
        bucket compiles one program; the loop itself stops at the wave's
        longest member (``_trip_count``), and the frame's tail past it is
        never read."""
        B = len(cids)
        idx = np.zeros((B, self.num_steps, self.bs_pad), np.int32)
        valid = np.zeros((B, self.num_steps, self.bs_pad), np.float32)
        nvalid = np.zeros((B, self.num_steps), np.float32)
        for i, (c, s) in enumerate(zip(cids, seeds)):
            sched = epoch_batch_indices(int(self.sizes[c]), self.local_epochs,
                                        self.batch_size, int(s))
            st, bs = sched.shape
            idx[i, :st, :bs] = sched
            valid[i, :st, :bs] = 1.0
            nvalid[i, :st] = bs
        counts = np.maximum(nvalid, 1.0)
        return idx, valid, counts, nvalid

    def _trip_count(self, cids: np.ndarray, nvalid: np.ndarray) -> np.int32:
        """The wave's local-SGD trip count: its longest real member's steps
        (bucket padding rows are added after, and do not count)."""
        n_steps = int(self.steps_per_client[cids].max())
        assert n_steps == int((nvalid > 0.0).sum(axis=1).max()), \
            "trip count disagrees with the built schedules"
        return np.int32(n_steps)

    def cohort_update(self, params_stack: jnp.ndarray, cids: Sequence[int],
                      lrs: Sequence[float], seeds: Sequence[int]
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Train the cohort; returns (deltas, new_params), both (B, d).

        ``params_stack`` holds each member's dispatch snapshot (its anchor
        for prox/align); ``lrs``/``seeds`` are per-member, matching what the
        legacy loop would have used for that dispatch.

        The host's part (schedules, padding, uploads, the compiled call's
        enqueue) is the ``cohort.enqueue`` span; each call records one
        ``cohort.wave``: ``members``, bucketed ``rows``, the members' real
        local ``steps``, the ``schedule`` every row runs (the trip count of
        this wave's loop: its longest member's steps, not the engine-wide
        ``num_steps``), and the real ``samples`` those steps train on.
        """
        with obs.span("cohort.enqueue"):
            B = int(params_stack.shape[0])
            assert B >= 1
            cids = np.asarray(cids, np.int32)
            idx, valid, counts, nvalid = self._schedules(cids,
                                                         np.asarray(seeds))
            # per-(member, step) learning rate: the member's lr on real
            # steps, 0 on padded steps (making them exact no-ops)
            lr_steps = (np.asarray(lrs, np.float64)[:, None]
                        * (nvalid > 0.0)).astype(np.float32)
            n_steps = self._trip_count(cids, nvalid)
            Bp = bucket_size(B, self._data_kind)
            steps = self.steps_per_client[cids]
            obs.record("cohort.wave", members=B, rows=Bp,
                       steps=int(steps.sum()), schedule=int(n_steps),
                       samples=int((steps * np.minimum(
                           self.batch_size, self.sizes[cids])).sum()))
            pad = Bp - B
            if pad > 0:
                def padded(a):
                    return np.concatenate(
                        [a, np.zeros((pad,) + a.shape[1:], a.dtype)])

                params_stack = jnp.concatenate(
                    [params_stack, jnp.zeros((pad, params_stack.shape[1]),
                                             params_stack.dtype)])
                idx, valid, lr_steps = map(padded, (idx, valid, lr_steps))
                counts = np.concatenate(
                    [counts, np.ones((pad,) + counts.shape[1:],
                                     counts.dtype)])
            deltas, w = self._launch(params_stack, cids, pad, idx, valid,
                                     counts, lr_steps, n_steps)
            return deltas[:B], w[:B]

    def _launch(self, params_stack, cids, pad, idx, valid, counts, lr_steps,
                n_steps):
        """The compiled wave over the resident slab, which each member
        indexes by its client id (padded rows by client 0)."""
        cids = np.concatenate([cids, np.zeros((pad,), cids.dtype)])
        args = (params_stack, jnp.asarray(cids), jnp.asarray(idx),
                jnp.asarray(valid), jnp.asarray(counts),
                jnp.asarray(lr_steps))
        if self.mesh is not None:
            ax = wave_axis(self.mesh, self.cohort_axis,
                           int(params_stack.shape[0]))
            args = tuple(
                jax.device_put(a, NamedSharding(
                    self.mesh, P(*([ax] + [None] * (a.ndim - 1)))))
                for a in args)
            # the trip count is a 0-d scalar every device reads whole
            n_steps = jax.device_put(n_steps, NamedSharding(self.mesh, P()))
        return self._run(self.x, self.y, *args, n_steps)

    def sweep_update(self, params_stack: jnp.ndarray, cids: Sequence[int],
                     lrs: Sequence[float], seeds_per_lane: np.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Train one wave for all S sweep lanes in ONE compiled call.

        ``params_stack`` is the ``(S, B, d)`` stack of per-lane dispatch
        snapshots; ``cids``/``lrs`` are shared across lanes (the event
        timeline is lane-invariant); ``seeds_per_lane`` is ``(S, B)`` —
        per-lane client-shuffle seeds for the wave's members. Returns
        ``(deltas, new_params)``, both ``(S, B, d)``. Lane ``s`` is
        arithmetically identical to ``cohort_update`` on that lane's
        snapshots/seeds: the member program is the same, vmapped once more
        over the lane axis.
        """
        S, B = int(params_stack.shape[0]), int(params_stack.shape[1])
        assert B >= 1 and S >= 1
        assert self.mesh is None, "sweeps run single-device (no mesh support)"
        cids = np.asarray(cids, np.int32)
        seeds_per_lane = np.asarray(seeds_per_lane)
        # Schedule shapes (valid masks, per-step counts) depend only on
        # client sizes — lane-invariant; only the index permutations are
        # per-lane. Lanes sharing a seed row share one schedule build.
        built = {}
        idx = np.zeros((S, B, self.num_steps, self.bs_pad), np.int32)
        valid = counts = nvalid = None
        for s in range(S):
            key = tuple(int(v) for v in seeds_per_lane[s])
            if key not in built:
                built[key] = self._schedules(cids, seeds_per_lane[s])
            idx[s], valid, counts, nvalid = built[key]
        lr_steps = (np.asarray(lrs, np.float64)[:, None]
                    * (nvalid > 0.0)).astype(np.float32)
        n_steps = self._trip_count(cids, nvalid)
        Bp = bucket_size(B, self._data_kind)
        if Bp > B:
            pad = Bp - B

            def padded(a, fill=0):
                ext = np.full((pad,) + a.shape[1:], fill, a.dtype)
                return np.concatenate([a, ext])

            params_stack = jnp.concatenate(
                [params_stack,
                 jnp.zeros((S, pad, params_stack.shape[2]),
                           params_stack.dtype)], axis=1)
            idx = np.concatenate(
                [idx, np.zeros((S, pad) + idx.shape[2:], idx.dtype)], axis=1)
            cids = padded(cids)
            valid, lr_steps = padded(valid), padded(lr_steps)
            counts = np.concatenate(
                [counts, np.ones((pad,) + counts.shape[1:], counts.dtype)])
        deltas, w = self._run_lanes(
            self.x, self.y, params_stack, jnp.asarray(cids),
            jnp.asarray(idx), jnp.asarray(valid), jnp.asarray(counts),
            jnp.asarray(lr_steps), n_steps)
        return deltas[:, :B], w[:, :B]


class StreamingCohortEngine(CohortEngine):
    """The cohort engine over streamed client slabs (population scale).

    Same compiled member program as ``CohortEngine`` except the data
    arrives per wave: instead of indexing a resident ``(C, n_max, ...)``
    slab by client id inside the jit, each member receives its own
    ``(n_max, ...)`` rows, gathered by a ``data.loader.ClientSlabStore``
    (cached device shards + on-demand row uploads). Members train on
    exactly the rows the monolithic slab holds for them and the batch
    schedules come from the same ``epoch_batch_indices`` stream, so the two
    engines agree to float tolerance — the streaming digest-parity tests
    pin this. Memory is bounded by the store's shard geometry, not by C.

    Single-device by construction (the simulator rejects mesh +
    streaming); the lane variant mirrors ``sweep_update`` with the wave's
    row slab shared across lanes.
    """

    def __init__(self, cfg: ModelConfig, store, spec: tu.FlatSpec,
                 template_params, *, local_epochs: int = 5,
                 batch_size: int = 64, prox: float = 0.0,
                 align: float = 0.0, member_kernel: str = "vmap"):
        fam = registry.get_family(cfg)
        self._data_kind = fam.data_kind
        self.cfg = cfg
        self.spec = spec
        self.local_epochs = int(local_epochs)
        self.batch_size = int(batch_size)
        self.prox = float(prox)
        self.align = float(align)
        if member_kernel not in member_math.MODES:
            raise ValueError(f"member_kernel must be one of "
                             f"{member_math.MODES}, got {member_kernel!r}")
        self.member_kernel = member_kernel
        self.store = store
        self.sizes = np.asarray(store.sizes, np.int64)
        self.mesh = None
        self.cohort_axis = None
        bs_c = np.minimum(self.batch_size, self.sizes)
        self.steps_per_client = (self.local_epochs
                                 * (self.sizes // bs_c)).astype(int)
        self.num_steps = int(self.steps_per_client.max())
        self.bs_pad = int(bs_c.max())
        key = (cfg, spec, self.prox, self.align, fam, member_kernel, "rows")
        if key not in _RUN_CACHE:
            _RUN_CACHE[key] = self._build_rows(cfg, spec, self.prox,
                                               self.align, fam, member_kernel)
        self._run_rows, self._run_rows_lanes = _RUN_CACHE[key]

    @staticmethod
    def _build_rows(cfg, spec, prox, align, fam, member_kernel="vmap"):
        # the same member program as CohortEngine._build, minus the in-jit
        # x_all[cid] gather: each member receives its own rows
        over_members = jax.vmap(
            CohortEngine._local_sgd(cfg, spec, prox, align, fam,
                                    member_kernel),
            in_axes=(0, 0, 0, 0, 0, 0, 0, None))

        @jax.jit
        def run(x_rows, y_rows, params_stack, idx, valid, counts, lr_steps,
                n_steps):
            w = over_members(x_rows, y_rows, params_stack, idx, valid,
                             counts, lr_steps, n_steps)
            return w - params_stack, w

        @jax.jit
        def run_lanes(x_rows, y_rows, params_stack, idx, valid, counts,
                      lr_steps, n_steps):
            # lanes share the wave's row slab, schedules shapes, lr and trip
            # count; the snapshots and index permutations are per-lane
            w = jax.vmap(over_members,
                         in_axes=(None, None, 0, 0, None, None, None, None))(
                x_rows, y_rows, params_stack, idx, valid, counts, lr_steps,
                n_steps)
            return w - params_stack, w

        return run, run_lanes

    def _wave_rows(self, cids: np.ndarray, pad: int):
        """The wave's (Bp, n_max, ...) device row slab, zero-padded rows
        for bucket-grid members (their lr is 0 — exact no-ops)."""
        x, y = self.store.gather(cids)
        if pad > 0:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            y = jnp.concatenate(
                [y, jnp.zeros((pad,) + y.shape[1:], y.dtype)])
        return x, y

    def _launch(self, params_stack, cids, pad, idx, valid, counts, lr_steps,
                n_steps):
        """The compiled wave over the members' own streamed rows."""
        x, y = self._wave_rows(cids, pad)
        return self._run_rows(x, y, params_stack, jnp.asarray(idx),
                              jnp.asarray(valid), jnp.asarray(counts),
                              jnp.asarray(lr_steps), n_steps)

    def sweep_update(self, params_stack: jnp.ndarray, cids: Sequence[int],
                     lrs: Sequence[float], seeds_per_lane: np.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        S, B = int(params_stack.shape[0]), int(params_stack.shape[1])
        assert B >= 1 and S >= 1
        cids = np.asarray(cids, np.int32)
        seeds_per_lane = np.asarray(seeds_per_lane)
        built = {}
        idx = np.zeros((S, B, self.num_steps, self.bs_pad), np.int32)
        valid = counts = nvalid = None
        for s in range(S):
            key = tuple(int(v) for v in seeds_per_lane[s])
            if key not in built:
                built[key] = self._schedules(cids, seeds_per_lane[s])
            idx[s], valid, counts, nvalid = built[key]
        lr_steps = (np.asarray(lrs, np.float64)[:, None]
                    * (nvalid > 0.0)).astype(np.float32)
        n_steps = self._trip_count(cids, nvalid)
        Bp = bucket_size(B, self._data_kind)
        pad = Bp - B
        x, y = self._wave_rows(cids, pad)
        if pad > 0:
            def padded(a):
                return np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])

            params_stack = jnp.concatenate(
                [params_stack,
                 jnp.zeros((S, pad, params_stack.shape[2]),
                           params_stack.dtype)], axis=1)
            idx = np.concatenate(
                [idx, np.zeros((S, pad) + idx.shape[2:], idx.dtype)], axis=1)
            valid, lr_steps = padded(valid), padded(lr_steps)
            counts = np.concatenate(
                [counts, np.ones((pad,) + counts.shape[1:], counts.dtype)])
        deltas, w = self._run_rows_lanes(
            x, y, params_stack, jnp.asarray(idx), jnp.asarray(valid),
            jnp.asarray(counts), jnp.asarray(lr_steps), n_steps)
        return deltas[:, :B], w[:, :B]
