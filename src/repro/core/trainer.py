"""ElasticTrainer: the generic mega-batch training engine.

The trainer contains **no algorithm-specific branching**: everything that
distinguishes Adaptive SGD from its baselines (K-step averaging, gradient
aggregation, CROSSBOW model averaging, single-worker SGD, delayed-sync
adaptive batching, ...) lives in a pluggable strategy resolved from
``cfg.algorithm`` by the ``core/algorithms`` registry. The engine drives
the strategy through five hooks (DESIGN.md §4):

  init_state_extras → plan → round_transforms (traced) → merge → adapt

A *model* is a ``TrainableModel`` (models/protocol.py): ``init``,
``loss_fn``, optional ``sparse_grad_fn`` whose embedding-style grad leaves
are RowSparseGrad (DESIGN.md §3) — the trainer then runs the row-sparse
update path (``sparse_grads=False`` forces dense autodiff, the
differential oracle). The legacy ``{'init': ..., 'loss_fn': ...}`` dict is
still accepted and coerced. A *provider* supplies padded fixed-slot
batches (data/providers.py).

Placement (DESIGN.md §5, selected by ``cfg.placement``):
  * ``vmap`` (default) — every replica lives in one device program,
    vectorized over the leading R dim. Single-device; the differential
    oracle for the sharded mode.
  * ``sharded`` — the leading replica dim of params/momentum/batches is
    laid out over a 1-D ``replica`` device mesh with ``shard_map``: each
    shard runs its own replicas' rounds (same traced round_body, same
    jit/donation semantics per shard), and the barrier merge /
    replica-norm reductions become collectives (psum / axis-gather) over
    the mesh axis. Algorithm hooks are placement-agnostic: cross-replica
    math inside RoundTransforms goes through the placement-aware helpers
    (core/algorithms/base.py ``replica_axis_name``).

Execution engines (DESIGN.md §1):
  * ``scan`` (default) — device-resident mega-batch engine. The whole plan
    is pre-stacked into (n_rounds, R, ...) arrays and all rounds run inside
    one jitted ``jax.lax.scan`` with replica/momentum buffers donated;
    loss/accuracy/n_valid accumulate on device, so the host syncs once per
    mega-batch instead of once per round.
  * ``legacy_loop`` — a per-round host loop over the same staged
    mega-batch (one jitted round dispatch + metric read per round). The
    test reference for the scan engine (tests/test_megabatch_engine.py).

Both engines run inside the one mega-batch path (``run_megabatch``: stage,
dispatch, adapt, stage the next, collect, merge) and trace the *same*
``round_body`` — including the algorithm's ``RoundTransforms`` (gradient
transform + post-round correction) — so the strategy hooks behave
identically under either executor.

Elastic membership (DESIGN.md §6): the replica count R may change between
mega-batches — ``resize`` re-plans (scheduler + speed model at the new R),
re-shards (replica mesh + cached shard_map executors), and carries state
(final normalized merge folds leaving replicas in; joiners clone the merged
global with zero momentum). ``run(resize_schedule=...)`` drives it from a
mega-batch→R schedule; jit caches are reused so revisiting a population
shape recompiles nothing.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ElasticConfig
from repro.core import adaptive_sgd as asgd
from repro.core import algorithms
from repro.core.heterogeneity import (
    CostModel, MeasuredSpeedModel, ShardWindowTimer, SpeedModel,
)
from repro.core.scheduler import DynamicScheduler
from repro.data.batcher import StagingBuffers
from repro.kernels.weighted_merge.ops import flat_leaves
from repro.models.protocol import TrainableModel, as_trainable_model
from repro.optim.sgd import SGDConfig, init_momentum, sgd_update
from repro.sharding.rules import REPLICA_AXIS, ReplicaMeshPool, replica_spec
from repro.utils import tree as tu
from repro.utils.logging import MetricsLog, log, span as trace_span

PyTree = Any

ENGINES = ("scan", "legacy_loop")
PLACEMENTS = ("vmap", "sharded")


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class ElasticState:
    replicas: PyTree                 # leaves (R, ...)
    global_model: Optional[PyTree]
    prev_global: Optional[PyTree]
    momentum: Optional[PyTree]
    b: np.ndarray                    # per-replica batch size (may be fractional)
    lr: np.ndarray                   # per-replica learning rate
    megabatch_idx: int = 0


@dataclass
class _PlanView:
    """The slice of ElasticState the planning hook reads (``algo.plan``
    implementations consume only b / lr / the index) — lets the overlap
    pipeline plan mega-batch N+1 from ``adapt``'s outputs before N's merged
    state object exists."""

    b: np.ndarray
    lr: np.ndarray
    megabatch_idx: int


@dataclass
class _StagedMegaBatch:
    """A prefetched mega-batch: plan + device-resident arrays + the cursor
    snapshot that makes it revocable (DESIGN.md §8).

    ``snapshot`` holds the provider stream state, virtual-clock vector, and
    (simulated) speed-model state captured *before* the staging plan ran:
    ``invalidate_prefetch`` rolls the trainer back to it so a resize / fleet
    event replans from unconsumed cursors, and ``checkpoint_payload``
    substitutes it so a checkpoint taken mid-prefetch restores to *replay*
    the staged batch instead of skipping it.
    """

    plan: Any                 # MegaBatchPlan
    batches: dict             # device arrays, leaves (n_rounds, R, ...)
    mask: Any                 # device (n_rounds, R) float32 update mask
    lr_dev: Any               # device (R,) float32 learning rates
    b: np.ndarray             # host copies the plan was made for (validation)
    lr: np.ndarray
    megabatch_idx: int
    n_replicas: int
    slot_id: Optional[int]    # StagingBuffers slot, None = unbuffered
    snapshot: dict            # pre-staging cursor state (see above)
    counters: dict            # slot counts of the plan (``_slot_counters``)


@dataclass
class ElasticTrainer:
    model: TrainableModel | dict
    provider: Any
    cfg: ElasticConfig
    sgd: SGDConfig = field(default_factory=SGDConfig)
    base_lr: float = 0.05
    speed: Optional[SpeedModel] = None
    merge_cost: float = 5e-3         # virtual seconds per merge (all-reduce)
    keep_global_copies: bool = True  # False = paper §4 memory-lean merging
    engine: str = "scan"             # 'scan' | 'legacy_loop' (see module doc)
    round_bucket: bool = True        # pad n_rounds to pow2: bounds recompiles
    sparse_grads: bool = True        # use the model's row-sparse grad path if
                                     # it provides one; False = dense autodiff
                                     # (the differential oracle, DESIGN.md §3)
    guard_nonfinite: bool = True     # quarantine NaN/Inf replicas before the
                                     # merge (DESIGN.md §7); numerically inert
                                     # while every replica stays finite
    overlap: bool = True             # overlapped mega-batch pipeline
                                     # (DESIGN.md §8): stage N+1 + dispatch
                                     # eval while N executes. False = stage
                                     # each mega-batch just in time and
                                     # evaluate synchronously (same path)
    mesh: Optional[Mesh] = None      # replica mesh for cfg.placement='sharded'
                                     # (None = build one over the local devices)
    multihost: Optional[Any] = None  # launch.multihost.MultihostContext: span
                                     # this trainer across processes
                                     # (DESIGN.md §10). None = single process.
    seed: int = 0

    def __post_init__(self):
        if self.engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {self.engine!r}")
        if self.cfg.placement not in PLACEMENTS:
            raise ValueError(
                f"cfg.placement must be one of {PLACEMENTS}, got {self.cfg.placement!r}"
            )
        self.model = as_trainable_model(self.model)
        self.algo = algorithms.get(self.cfg.algorithm)
        # process spanning (DESIGN.md §10). Host span: every process runs
        # the identical deterministic host loop at the *global* R but holds
        # only its contiguous block of replica slots on a process-local
        # mesh; cross-process reductions go through the context's file
        # exchange. Device span: the mesh just covers the global device
        # list — the SPMD executors are unchanged.
        self._span = None
        self._global_put = False
        if self.multihost is not None:
            if self.multihost.spanning == "host":
                self._setup_host_span()
            else:
                self._global_put = True
                if self.cfg.placement != "sharded":
                    raise ValueError(
                        "device-span multihost needs cfg.placement='sharded'"
                    )
        self._mesh_pool = None
        self._exec_cache = {}            # shard count -> sharded executors
        self._span_exec_cache = {}       # shard count -> span partial-merge
        if self.cfg.placement == "sharded":
            if self.mesh is None:
                devices = (
                    self.multihost.global_devices()
                    if self._global_put else None
                )
                self._mesh_pool = ReplicaMeshPool(devices)
                self.mesh = self._mesh_pool.mesh_for(self._mesh_width())
            else:
                if REPLICA_AXIS not in self.mesh.shape:
                    raise ValueError(
                        f"sharded placement needs a {REPLICA_AXIS!r} mesh axis, "
                        f"got {tuple(self.mesh.axis_names)}"
                    )
                if self.cfg.n_replicas % self.mesh.shape[REPLICA_AXIS] != 0:
                    raise ValueError(
                        f"n_replicas={self.cfg.n_replicas} not divisible by the "
                        f"replica mesh ({self.mesh.shape[REPLICA_AXIS]} devices)"
                    )
                # a resize may need meshes of other shard counts; they are
                # drawn from the same devices the caller chose
                self._mesh_pool = ReplicaMeshPool(list(self.mesh.devices.flat))
                self._mesh_pool.adopt(self.mesh)
        if self.speed is None:
            self.speed = SpeedModel(self.cfg.n_replicas, seed=self.seed)
        self.cost = CostModel(self.speed)
        self.scheduler = DynamicScheduler(self.cfg, self.cost)
        self._eval_batches = None        # pre-staged device test batches
        self._eval_batches_src = None    # pins the staged list + its batches
        self._eval_batches_key = None    # content fingerprint of that list
        self._staged = None              # prefetched _StagedMegaBatch
        self._current_megabatch = 0      # index the host spans carry
        self._staging = StagingBuffers() # double-buffered host staging slots
        # per-shard measured timing (DESIGN.md §8): only the sharded
        # executors carry the debug-callback markers, and only a measured
        # speed model consumes the windows. Built before the executors,
        # which close over it.
        self._shard_timer = (
            ShardWindowTimer()
            if self.cfg.placement == "sharded"
            and isinstance(self.speed, MeasuredSpeedModel)
            else None
        )
        # the parameters' shapes, for the counters (no device work); rows
        # w1 is stored with beyond the model's features (DESIGN.md §3)
        self._param_shapes = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0))
        )
        n_features = getattr(self.model.config, "n_features", None)
        self._w1_pad_rows = None
        if n_features is not None:
            self._w1_pad_rows = self._param_shapes["w1"].shape[0] - n_features
        self._build_jits()

    # ------------------------------------------------------------------
    # process spanning (DESIGN.md §10)
    # ------------------------------------------------------------------
    def _setup_host_span(self) -> None:
        """Validate + adopt a host-span multihost context: this process
        will run the global deterministic loop but execute only its own
        contiguous replica block. The constraints are structural, not
        incidental: vmap/legacy have no per-shard executors to localize;
        a measured speed model would feed each process different observed
        factors and fork the deterministic plan; algorithms whose round
        transforms reduce *across* replicas every round would need a
        cross-process collective inside the jitted scan, which the host
        exchange cannot provide."""
        ctx = self.multihost
        if self.cfg.placement != "sharded":
            raise ValueError("host-span multihost needs cfg.placement='sharded'")
        if self.engine != "scan":
            raise ValueError("host-span multihost needs engine='scan'")
        if self.mesh is not None:
            raise ValueError(
                "host-span multihost builds its own process-local mesh; "
                "do not pass one"
            )
        if isinstance(self.speed, MeasuredSpeedModel):
            raise ValueError(
                "host-span multihost needs the simulated SpeedModel: every "
                "process must plan from identical speed factors"
            )
        if getattr(self.algo, "round_collectives", False):
            raise ValueError(
                f"algorithm {self.cfg.algorithm!r} reduces across replicas "
                "inside every round (round_collectives=True); its collectives "
                "cannot span processes on the host-exchange path"
            )
        ctx.assign_slots(self.cfg.n_replicas)
        self._span = ctx

    def _mesh_width(self) -> int:
        """Replica count the local mesh must cover: the process-local
        block under host span, the global R otherwise."""
        return (
            self._span.local_count() if self._span is not None
            else self.cfg.n_replicas
        )

    def _span_slice(self) -> slice:
        """This process's rows of any global (R, ...) array."""
        if self._span is None:
            return slice(None)
        lo, hi = self._span.local_bounds()
        return slice(lo, hi)

    def process_slots(self, pid: int) -> Optional[list[int]]:
        """Global replica slots owned by fleet process ``pid`` (None when
        not spanning or unknown) — the FleetController's resolution hook
        for process-grain fault events."""
        if self._span is None:
            return None
        return self._span.slots_of(pid)

    # ------------------------------------------------------------------
    # jitted device functions
    # ------------------------------------------------------------------
    def _build_jits(self):
        loss_fn = self.model.loss_fn
        # Sparse-gradient path (DESIGN.md §3): the model may expose
        # ((loss, aux), grads) directly, with embedding-style grads as
        # RowSparseGrad leaves — same calling convention as value_and_grad.
        sparse_fn = self.model.sparse_grad_fn if self.sparse_grads else None
        grad_fn = sparse_fn or jax.value_and_grad(loss_fn, has_aux=True)

        # Built once per trainer: RoundTransforms is a static jit argument
        # (hashed by callable identity), so a stable object keeps the jit
        # cache stable across mega-batches.
        self._transforms = self.algo.round_transforms(self.cfg)

        # Collective axis of the sharded placement: inside shard_map the
        # leading R dim of every leaf covers only this shard's replicas, so
        # cross-replica reductions (metrics, live-gating, merges) must fold
        # the other shards in over this axis. None under vmap — every
        # reduction below then lowers exactly as the single-program
        # original. Same helper the algorithm hooks use, so engine and
        # strategies can never disagree on the axis.
        axis = algorithms.replica_axis_name(self.cfg)

        def round_body(replicas, momentum, batch, lr_vec, update_mask, transforms):
            """One lockstep round; shared by both engines (traced inside the
            scan for the device-resident engine, jitted alone for legacy)
            and by both placements (vectorized whole under 'vmap', mapped
            over the replica mesh under 'sharded'). The algorithm's
            RoundTransforms trace here, so strategy behavior is
            engine-independent by construction."""
            (loss, aux), grads = jax.vmap(grad_fn)(replicas, batch)
            if transforms.grad_transform is not None:
                grads = transforms.grad_transform(grads, update_mask)
            new_replicas, new_momentum = sgd_update(
                replicas,
                grads,
                lr_vec,
                self.sgd,
                momentum_state=momentum,
                update_mask=update_mask,
                replica_dim=True,
            )
            if transforms.post_round is not None:
                adjusted = transforms.post_round(new_replicas)
                # fully-masked (bucket-padding) rounds must be exact no-ops;
                # liveness spans the whole mesh — a shard whose local
                # replicas are all masked must still apply the correction
                # when a replica elsewhere is live (its collectives traced
                # unconditionally above, so every shard participates)
                live_local = update_mask.max()
                live = (
                    jax.lax.pmax(live_local, axis) if tu.spans_shards(axis)
                    else live_local
                ) > 0
                new_replicas = tu.tree_map(
                    lambda a, r: jnp.where(live, a, r), adjusted, new_replicas
                )
            metrics = {
                "loss": loss,
                "accuracy": aux["accuracy"],
                "n_valid": aux["n_valid"],
            }
            return new_replicas, new_momentum, metrics

        def make_megabatch_fn(raw_stats):
            """Scan-fused mega-batch: all rounds in one device program.

            ``batches`` leaves and ``update_mask`` carry a leading
            (n_rounds,) scan dim. Per-round metrics reduce on device into
            4 scalars — the only values the host ever pulls. Under the
            sharded placement the raw per-round sums are psum-ed over the
            replica axis first, so every shard (and the host) sees
            whole-population metrics.

            ``raw_stats`` (host span, DESIGN.md §10): the psum above only
            covers the *local* mesh, so normalizing in-program would bake
            in per-process denominators. The variant returns the per-round
            raw sums instead — ``{"round_sums": (n_rounds, 4)}`` — and the
            host completes the reduction across processes with the exact
            same arithmetic (``_finish_metrics``). The default variant is
            byte-identical to the pre-span engine.
            """

            def megabatch_fn(replicas, momentum, batches, lr_vec,
                             update_mask, transforms):
                def body(carry, xs):
                    reps, mom = carry
                    batch, mask = xs
                    new_reps, new_mom, m = round_body(
                        reps, mom, batch, lr_vec, mask, transforms
                    )
                    sums = jnp.stack(
                        [
                            jnp.sum(m["loss"] * mask),
                            jnp.sum(m["accuracy"] * mask),
                            jnp.sum(m["n_valid"] * mask),
                            jnp.sum(mask),
                        ]
                    )
                    sums = tu.replica_all_sum(sums, axis)
                    if raw_stats:
                        return (new_reps, new_mom), sums
                    denom = jnp.maximum(sums[3], 1.0)
                    stats = jnp.stack(
                        [
                            sums[0] / denom,
                            sums[1] / denom,
                            sums[2],
                            (sums[3] > 0).astype(jnp.float32),
                        ]
                    )
                    return (new_reps, new_mom), stats

                (replicas, momentum), stats = jax.lax.scan(
                    body, (replicas, momentum), (batches, update_mask)
                )
                if raw_stats:
                    return replicas, momentum, {"round_sums": stats}
                live = stats[:, 3]
                n_live = jnp.maximum(jnp.sum(live), 1.0)
                metrics = {
                    "loss": jnp.sum(stats[:, 0]) / n_live,
                    "accuracy": jnp.sum(stats[:, 1]) / n_live,
                    "n_valid": jnp.sum(stats[:, 2]),
                    "rounds_live": jnp.sum(live),
                }
                return replicas, momentum, metrics

            return megabatch_fn

        megabatch_fn = make_megabatch_fn(self._span is not None)

        # Donate the replica/momentum buffers: the engine updates them in
        # place on device (no copy per mega-batch). CPU XLA cannot donate —
        # skip there to avoid a warning per compile.
        donate = (0, 1) if jax.default_backend() in ("tpu", "gpu") else ()

        def merge_fn(replicas, alphas, global_model, prev_global, gamma):
            # under shard_map ``replicas``/``alphas`` are this shard's
            # slices; normalized_merge completes the weighted sum with a
            # psum over the replica axis and broadcasts locally
            new_global = asgd.normalized_merge(
                replicas, alphas, global_model, prev_global, gamma,
                axis_name=axis,
            )
            R_local = jax.tree_util.tree_leaves(replicas)[0].shape[0]
            new_replicas = tu.tree_broadcast_replicas(new_global, R_local)
            return new_global, new_replicas

        if axis is None:
            # Built once per trainer and NEVER rebuilt on resize: R enters
            # these programs only through leaf shapes, so jax.jit's own
            # cache keys them per population size — a resize back to a
            # previously-seen R recompiles nothing (DESIGN.md §6).
            self._round = jax.jit(round_body, static_argnames=("transforms",))
            self._megabatch = jax.jit(
                megabatch_fn,
                static_argnames=("transforms",),
                donate_argnums=donate,
            )
            self._merge = jax.jit(merge_fn, static_argnames=("gamma",))
            self._norms = jax.jit(lambda r: tu.tree_l2_norm_per_replica(r))
            self._eval = jax.jit(loss_fn)
        else:
            # the traced bodies are mesh-independent; shard_map binds them
            # to self.mesh per shard count, cached across resizes
            self._bodies = (round_body, megabatch_fn, merge_fn, loss_fn, donate)
            self._install_sharded_executors()

        def finite_rows(tree):
            """(R,) bool: replica i's leaves are all finite. Read-only — the
            non-finite guard's detection pass never perturbs the numerics of
            a healthy mega-batch (golden bit-identity)."""
            parts = [
                jnp.all(
                    jnp.isfinite(l.astype(jnp.float32)),
                    axis=tuple(range(1, l.ndim)),
                )
                for l in jax.tree_util.tree_leaves(tree)
            ]
            return jnp.all(jnp.stack(parts, 0), axis=0)

        self._finite_rows = jax.jit(finite_rows)

        if self._span is not None:
            # host-span momentum term: the exact f32 arithmetic of
            # normalized_merge's global-momentum step, applied to the
            # exchange-summed merged tree (every process computes it
            # identically from replicated inputs)
            def span_momentum(merged, g, gp, gamma):
                f32 = jnp.float32
                return tu.tree_map(
                    lambda m, a, b: (
                        m.astype(f32) + gamma * (a.astype(f32) - b.astype(f32))
                    ).astype(m.dtype),
                    merged, g, gp,
                )

            self._span_momentum = jax.jit(
                span_momentum, static_argnames=("gamma",)
            )

    def _install_sharded_executors(self):
        """Bind (or re-bind, after a resize) the engine entry points to the
        current ``self.mesh``, reusing previously built executors for a
        shard count seen before — their jit caches then key the new R only
        by leaf shapes, so revisiting a population shape recompiles
        nothing (DESIGN.md §6)."""
        key = int(self.mesh.shape[REPLICA_AXIS])
        execs = self._exec_cache.get(key)
        if execs is None:
            execs = self._build_sharded_executors(*self._bodies)
            self._exec_cache[key] = execs
        self._round, self._megabatch, self._merge, self._norms, self._eval = execs
        if self._span is not None:
            partial = self._span_exec_cache.get(key)
            if partial is None:
                mesh, s0 = self.mesh, replica_spec(0)
                # local share of the Alg.-2 weighted sum: psum over the
                # *local* mesh only; the exchange completes it (host span)
                partial = jax.jit(
                    jax.shard_map(
                        lambda r, a: asgd.normalized_merge(
                            r, a, None, None, 0.0, axis_name=REPLICA_AXIS
                        ),
                        mesh=mesh,
                        in_specs=(s0, s0),
                        out_specs=P(),
                        check_vma=False,
                    )
                )
                self._span_exec_cache[key] = partial
            self._span_partial = partial

    def _build_sharded_executors(self, round_body, megabatch_fn, merge_fn,
                                 loss_fn, donate):
        """shard_map the engine entry points over the 1-D replica mesh.

        The traced bodies are the *same* functions the vmap placement jits —
        only the leading R dim they see shrinks to this shard's replica
        slice, and the reductions gated on the axis name become real
        collectives. RoundTransforms cannot ride through shard_map as a jit
        static argument, so the stable per-trainer object is closed over
        instead (same jit-cache behavior; the wrappers assert call sites
        keep passing the identical object). Returns the executor tuple
        ``(round, megabatch, merge, norms, eval)``; the wrappers carry their
        underlying jitted callable as ``_jit`` for cache introspection.
        """
        transforms = self._transforms
        mesh = self.mesh
        s0, s1 = replica_spec(0), replica_spec(1)
        timer = self._shard_timer

        jit_round = jax.jit(
            jax.shard_map(
                lambda r, m, b, lr, mask: round_body(
                    r, m, b, lr, mask, transforms
                ),
                mesh=mesh,
                # state/batch leaves are (R, ...): the replica dim leads
                in_specs=(s0, s0, s0, s0, s0),
                # per-replica metric vectors gather back to (R,)
                out_specs=(s0, s0, s0),
                check_vma=False,
            )
        )

        def timed_megabatch(r, m, b, lr, mask):
            """Per-shard window markers (DESIGN.md §8): the start callback
            depends only on an input leaf so it schedules at program entry;
            the end callback depends on the reduced metrics so it fires
            after the scan. Numerically inert — traced in only when a
            measured speed model will consume the windows."""
            if timer is not None:
                idx = jax.lax.axis_index(REPLICA_AXIS)
                jax.debug.callback(  # jaxlint: disable=JL006 — ShardTimer window-open marker, the measured-speed observation path (DESIGN.md §8)
                    lambda s, _dep: timer.mark_start(s), idx, mask[0, 0]
                )
            out_r, out_m, metrics = megabatch_fn(r, m, b, lr, mask, transforms)
            if timer is not None:
                jax.debug.callback(  # jaxlint: disable=JL006 — ShardTimer window-close marker, paired with mark_start above
                    lambda s, _dep: timer.mark_end(s), idx, metrics["loss"]
                )
            return out_r, out_m, metrics

        jit_megabatch = jax.jit(
            jax.shard_map(
                timed_megabatch,
                mesh=mesh,
                # stacked batches/mask are (n_rounds, R, ...): dim 1 shards
                in_specs=(s0, s0, s1, s0, s1),
                # the psum-ed scalar metrics are replicated on every shard
                out_specs=(s0, s0, P()),
                check_vma=False,
            ),
            donate_argnums=donate,
        )

        def _round(replicas, momentum, batch, lr_vec, update_mask, transforms):
            assert transforms is self._transforms
            return jit_round(replicas, momentum, batch, lr_vec, update_mask)

        def _megabatch(replicas, momentum, batches, lr_vec, update_mask,
                       transforms):
            assert transforms is self._transforms
            return jit_megabatch(
                replicas, momentum, batches, lr_vec, update_mask
            )

        _round._jit = jit_round
        _megabatch._jit = jit_megabatch

        @functools.partial(jax.jit, static_argnames=("gamma",))
        def merge_sharded(replicas, alphas, global_model, prev_global, gamma):
            # per-shard weighted partials -> psum inside normalized_merge;
            # every shard holds the replicated new global (out_spec P()) and
            # its (R_local, ...) broadcast, reassembled to the full replica
            # tree. globals/prev ride in replicated; None pytrees are empty
            # and match the P() prefix spec trivially.
            return jax.shard_map(
                functools.partial(merge_fn, gamma=gamma),
                mesh=mesh,
                in_specs=(s0, s0, P(), P()),
                out_specs=(P(), s0),
                check_vma=False,
            )(replicas, alphas, global_model, prev_global)

        norms = jax.jit(
            jax.shard_map(
                tu.tree_l2_norm_per_replica,
                mesh=mesh,
                in_specs=(s0,),
                out_specs=s0,
                check_vma=False,
            )
        )
        # The global model is replicated over the mesh, and the compiler
        # cannot partition a Pallas kernel (the XML input layer on a TPU):
        # every shard evaluates the whole test batch.
        evaluate = jax.jit(
            jax.shard_map(
                loss_fn,
                mesh=mesh,
                in_specs=(P(), P()),
                out_specs=P(),
                check_vma=False,
            )
        )
        return _round, _megabatch, merge_sharded, norms, evaluate

    def compile_cache_size(self) -> int:
        """Total compiled-variant count across every engine executor built
        so far (all placements, all cached shard counts). The DESIGN.md §6
        zero-recompile contract is testable through this number: a resize
        back to a previously-seen population shape, followed by a
        mega-batch whose round count lands in a previously-seen pow2
        bucket, must leave it unchanged."""

        def size(fn):
            inner = getattr(fn, "_jit", fn)
            cache_size = getattr(inner, "_cache_size", None)
            return int(cache_size()) if cache_size is not None else 0

        if self._exec_cache:
            fns = [f for execs in self._exec_cache.values() for f in execs]
        else:
            fns = [self._round, self._megabatch, self._merge, self._norms,
                   self._eval]
        return sum(size(f) for f in fns)

    def lower_megabatch(self, state: ElasticState, n_rounds: int):
        """Lower the scan engine's mega-batch program for ``state``'s
        population and ``n_rounds`` rounds of b_max slots, without running
        it. ``state`` leaves may be arrays or ``jax.ShapeDtypeStruct``s.
        ``.compile()`` of the result gives the program's device memory
        (``memory_analysis()``) and its text — what a run would compile."""
        R = self.cfg.n_replicas
        spec = self.provider.staging_spec(n_rounds, R, self.cfg.b_max)
        batches = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in spec.items()}
        lr = jax.ShapeDtypeStruct((R,), jnp.float32)
        mask = jax.ShapeDtypeStruct((n_rounds, R), jnp.float32)
        args = (state.replicas, state.momentum, batches, lr, mask)
        sharded = getattr(self._megabatch, "_jit", None)
        if sharded is not None:
            return sharded.lower(*args)
        return self._megabatch.lower(*args, transforms=self._transforms)

    # ------------------------------------------------------------------
    # jitted tensor math exposed to Algorithm.merge implementations
    # ------------------------------------------------------------------
    def merge_models(self, replicas, alphas, global_model, prev_global, gamma):
        """Normalized merge (Alg. 2 tensor math, jitted): returns
        (new_global, replicas reset to it). gamma=0 / None globals skip the
        global-momentum term — a plain weighted average.

        Host span: ``alphas`` is the *global* (R,) weight vector while
        ``replicas`` holds only the local rows; the weighted sum completes
        across processes through the exchange (``_merge_spanning``)."""
        if self._span is not None:
            return self._merge_spanning(
                replicas, alphas, global_model, prev_global, gamma
            )
        return self._merge(
            replicas, jnp.asarray(alphas, jnp.float32),
            global_model, prev_global, gamma,
        )

    def _merge_spanning(self, replicas, alphas, global_model, prev_global,
                        gamma):
        """Algorithm 2's merge across processes (DESIGN.md §10).

        Each process computes its local share of the weighted sum on
        device (same f32 arithmetic as the in-mesh psum path — the only
        cross-process difference is float reassociation), then the file
        exchange sums the partials. The contributed alpha mass rides along:
        when a peer died mid-mega-batch its partial is simply absent, and
        scaling the sum by ``expected/contributed`` mass is exactly the
        crash semantics of ``remove_replicas`` — the dead replicas' merge
        weight redistributes proportionally over the survivors.
        """
        span = self._span
        lo, hi = span.local_bounds()
        a = np.asarray(alphas, np.float64)
        a_local = jnp.asarray(a[lo:hi], jnp.float32)
        part = self._span_partial(replicas, a_local)
        payload = {
            "partial": tu.tree_map(np.asarray, part),
            "mass": np.float64(a[lo:hi].sum()),
        }
        total, contributors = span.allreduce_sum("merge", payload)
        merged_np = total["partial"]
        if len(contributors) < len(span.active_processes()):
            expected = float(a.sum())
            contributed = float(total["mass"])
            if contributed <= 0.0:
                raise FloatingPointError(
                    "every process holding nonzero merge weight died "
                    "mid-mega-batch; nothing to merge"
                )
            scale = np.float32(expected / contributed)
            merged_np = tu.tree_map(
                lambda l: (l * scale).astype(l.dtype), merged_np
            )
        merged = tu.tree_map(jnp.asarray, merged_np)
        if (
            global_model is not None and prev_global is not None
            and gamma != 0.0
        ):
            merged = self._span_momentum(
                merged, global_model, prev_global, gamma=float(gamma)
            )
        new_replicas = tu.tree_broadcast_replicas(merged, hi - lo)
        new_replicas, _, merged, _ = self._place_state(
            new_replicas, None, merged, None
        )
        return merged, new_replicas

    def replica_norms(self, replicas) -> np.ndarray:
        """Per-replica L2 norms, read to the host (feeds Alg. 2's
        perturbation condition).
        Host span: local norms are bit-exact per replica (no cross-replica
        reduction), so an allgather reassembles the global (R,) vector; a
        dead peer's rows read 0 — its merge weight is redistributed at the
        merge anyway."""
        with trace_span("sync.norms", megabatch=self._current_megabatch):
            local = np.asarray(self._norms(replicas))
        if self._span is None:
            return local
        span = self._span
        local = local.astype(np.float64)
        gathered = span.allgather("norms", local)
        out = np.zeros(self.cfg.n_replicas, np.float64)
        for pid, arr in gathered.items():
            plo, phi = span.bounds_of(pid)
            out[plo:phi] = np.asarray(arr, np.float64)
        return out

    # ------------------------------------------------------------------
    # state init
    # ------------------------------------------------------------------
    def init_state(self) -> ElasticState:
        R = self.cfg.n_replicas
        rng = jax.random.PRNGKey(self.seed)
        params = self.model.init(rng)
        # host span: device trees hold only this process's replica block;
        # the host-side vectors (b, lr) always stay global
        replicas = tu.tree_broadcast_replicas(params, self._mesh_width())
        momentum = init_momentum(replicas, self.sgd)
        extras = self.algo.init_state_extras(
            self.cfg, params, self.keep_global_copies
        )
        b = np.asarray(extras.b, np.float64)
        lr = self.base_lr * b / self.cfg.b_max  # linear-scaling rule
        return ElasticState(
            replicas=replicas,
            global_model=extras.global_model,
            prev_global=extras.prev_global,
            momentum=momentum,
            b=b,
            lr=lr,
        )

    # ------------------------------------------------------------------
    # elastic membership: resize R between mega-batches (DESIGN.md §6)
    # ------------------------------------------------------------------
    def resize(self, state: ElasticState, new_R: int) -> ElasticState:
        """Change the replica count between mega-batches.

        The elasticity the paper's title promises beyond adaptive batch
        sizes: workers joining or leaving mid-run. Resizing is a
        re-plan / re-shard / carry-state barrier:

        * **merge first** — every *current* replica (including the ones
          about to leave) contributes a final normalized merge (weights
          ``b_i / sum(b)``, Algorithm 2 line 3 — between mega-batches the
          update counts are spent, so batch sizes are the availability
          signal), executed on the *old* executors before any re-shard.
          Leaving replicas' updates are therefore never dropped.
        * **carry state** — under the default ``resize_policy='merge'``
          the new population restarts from the merged global; under
          ``'preserve'`` (CROSSBOW) survivors keep their own diverged
          parameters and only joiners clone the merged global. Survivors
          keep their momentum buffers; joiners start with zero momentum.
          The global-momentum pair restarts (``prev_global := merged``) so
          Algorithm 2's momentum term never mixes pre/post-resize
          populations. Speed EMAs / simulated factors carry for survivors;
          joiners start at the homogeneous prior. Batch sizes and lrs
          resize through ``algo.resize_b`` (Algorithm 1 then resumes from
          them at the new R on the next ``adapt``).
        * **re-plan** — the scheduler adopts the new config; survivor
          virtual clocks carry, joiners enter at the barrier time.
        * **re-shard** — under ``placement='sharded'`` the replica mesh is
          re-drawn from the trainer's device pool and the state trees are
          device_put onto it. Executors (and their jit caches) are reused
          per shard count, and the vmap jits are never rebuilt at all, so
          a resize back to a previously-seen population shape recompiles
          nothing (``compile_cache_size``).

        Resolves through ``algo.resolve_n_replicas`` first (``single``
        turns any schedule into a no-op); ``resize_policy='fixed'`` raises.
        Returns the state to continue from — like ``run_megabatch``, treat
        the input state as consumed.
        """
        new_R = int(self.algo.resolve_n_replicas(int(new_R)))
        R = self.cfg.n_replicas
        if new_R == R:
            return state
        if self._span is not None:
            raise ValueError(
                "a host-span trainer changes membership at process grain "
                "(heartbeat-driven fleet events); generic resize() is "
                "unsupported (DESIGN.md §10)"
            )
        if new_R < 1:
            raise ValueError(f"cannot resize to {new_R} replicas")
        policy = getattr(self.algo, "resize_policy", "merge")
        if policy == "fixed":
            raise ValueError(
                f"algorithm {self.algo.name!r} pins its replica membership "
                f"(resize_policy='fixed'); cannot resize {R} -> {new_R}"
            )
        # a prefetched plan was made for the old R: revoke it and roll the
        # cursors back *before* any membership mutation (DESIGN.md §8). The
        # new_R == R early return above deliberately keeps the prefetch —
        # a constant schedule stays bit-identical to the unscheduled run.
        self.invalidate_prefetch()

        # ---- final normalized merge over the outgoing population ----
        alphas = np.asarray(state.b, np.float64)
        alphas = alphas / alphas.sum()
        merged, _ = self.merge_models(
            state.replicas, alphas, None, None, 0.0
        )

        # ---- carry parameters / momentum to the new population ----
        keep = min(R, new_R)

        def grown(l, g, fill):
            """(R, ...) leaf -> (new_R, ...): survivors' rows + fill rows."""
            parts = [l[:keep]]
            if new_R > keep:
                extra = (
                    jnp.broadcast_to(g[None], (new_R - keep,) + g.shape)
                    if fill == "global"
                    else jnp.zeros((new_R - keep,) + l.shape[1:], l.dtype)
                )
                parts.append(extra)
            return jnp.concatenate(parts, 0) if len(parts) > 1 else parts[0]

        if policy == "preserve":
            new_replicas = tu.tree_map(
                lambda l, g: grown(l, g, "global"), state.replicas, merged
            )
        else:  # 'merge': everyone restarts from the merged global
            new_replicas = tu.tree_broadcast_replicas(merged, new_R)
        new_momentum = None
        if state.momentum is not None:
            new_momentum = tu.tree_map(
                lambda l: grown(l, None, "zeros"), state.momentum
            )
        new_global = merged if state.global_model is not None else None
        new_prev = merged if state.prev_global is not None else None

        # ---- re-plan: config, batch plan, speeds, virtual clocks ----
        new_cfg = dataclasses.replace(self.cfg, n_replicas=new_R)
        new_b, new_lr = self.algo.resize_b(
            new_cfg, state.b, state.lr, self.base_lr
        )
        self._adopt_width(new_R)

        # ---- re-shard: new replica mesh + cached executors ----
        new_replicas, new_momentum, new_global, new_prev = self._place_state(
            new_replicas, new_momentum, new_global, new_prev
        )

        return ElasticState(
            replicas=new_replicas,
            global_model=new_global,
            prev_global=new_prev,
            momentum=new_momentum,
            b=np.asarray(new_b, np.float64),
            lr=np.asarray(new_lr, np.float64),
            megabatch_idx=state.megabatch_idx,
        )

    def _adopt_width(self, new_R: int) -> None:
        """Adopt a new replica count: config, speed model, scheduler, and —
        under the sharded placement — the replica mesh + cached executors.
        The population-agnostic half of ``resize``, reused by
        ``restore_checkpoint`` when the checkpointed width differs from the
        trainer's construction width."""
        self.cfg = dataclasses.replace(self.cfg, n_replicas=new_R)
        self.speed.resize(new_R)
        self.scheduler.resize(self.cfg)
        if self.cfg.placement == "sharded":
            # host span: the local mesh covers this process's block, whose
            # width survives process-grain eviction — same mesh, same
            # executor caches, zero recompiles
            self.mesh = self._mesh_pool.mesh_for(self._mesh_width())
            self._install_sharded_executors()

    def _place_state(self, replicas, momentum, global_model, prev_global):
        """device_put the state trees onto the current replica mesh
        (identity under the vmap placement)."""
        if self.cfg.placement != "sharded":
            return replicas, momentum, global_model, prev_global
        shard0 = NamedSharding(self.mesh, replica_spec(0))
        repl = NamedSharding(self.mesh, P())
        put0 = lambda l: self._put_leaf(l, shard0)  # noqa: E731
        putr = lambda l: self._put_leaf(l, repl)  # noqa: E731
        replicas = tu.tree_map(put0, replicas)
        if momentum is not None:
            momentum = tu.tree_map(put0, momentum)
        if global_model is not None:
            global_model = tu.tree_map(putr, global_model)
        if prev_global is not None:
            prev_global = tu.tree_map(putr, prev_global)
        return replicas, momentum, global_model, prev_global

    def _put_leaf(self, l, sharding):
        """Upload one leaf. Device span: the target sharding covers
        non-addressable devices, which plain ``device_put`` rejects —
        ``make_array_from_callback`` assembles the global array from the
        (identical, host-replicated) value every process holds."""
        if self._global_put:
            arr = np.asarray(l)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx]
            )
        return jax.device_put(l, sharding)

    def remove_replicas(
        self, state: ElasticState, indices, merge_leavers: bool = True
    ) -> ElasticState:
        """Evict specific replica slots between mega-batches (DESIGN.md §7).

        ``resize`` only drops *tail* rows, so targeted eviction first
        permutes survivors to the front (every per-replica array — state
        rows, b/lr, speed factors/EMAs, virtual clocks — moves with its
        replica), then shrinks.

        ``merge_leavers`` encodes the fault semantics: a *preempted*
        replica got notice, so its updates fold into the final normalized
        merge like any graceful leaver (True); a *crashed or poisoned*
        replica must be excluded — its rows are zeroed and its merge weight
        set to 0, so Algorithm 2's normalization redistributes b_i over the
        survivors and a NaN payload can never reach the weighted sum
        (0 * NaN is NaN, hence the explicit zeroing).
        """
        R = self.cfg.n_replicas
        drop = sorted({int(i) for i in indices})
        if not drop:
            return state
        bad = [i for i in drop if i < 0 or i >= R]
        if bad:
            raise ValueError(f"replica indices {bad} out of range for R={R}")
        if len(drop) >= R:
            raise ValueError(
                f"cannot remove all {R} replicas (removal of {drop})"
            )
        if self._span is not None:
            return self._remove_replicas_spanning(state, drop, merge_leavers)
        # the permutation below moves speed factors / clocks with their
        # replica — a prefetched plan consumed them in the old order
        self.invalidate_prefetch()
        survivors = [i for i in range(R) if i not in set(drop)]
        perm = survivors + drop

        if perm != list(range(R)):
            p = jnp.asarray(perm)
            take = lambda l: jnp.take(l, p, axis=0)  # noqa: E731
            state = ElasticState(
                replicas=tu.tree_map(take, state.replicas),
                global_model=state.global_model,
                prev_global=state.prev_global,
                momentum=(
                    tu.tree_map(take, state.momentum)
                    if state.momentum is not None else None
                ),
                b=np.asarray(state.b, np.float64)[perm],
                lr=np.asarray(state.lr, np.float64)[perm],
                megabatch_idx=state.megabatch_idx,
            )
            self.speed.permute(perm)
            self.scheduler.clock.permute(perm)

        if not merge_leavers:
            keep = R - len(drop)
            mask = jnp.arange(R) < keep
            zero_tail = lambda l: jnp.where(  # noqa: E731
                mask.reshape((-1,) + (1,) * (l.ndim - 1)), l, jnp.zeros_like(l)
            )
            b = np.asarray(state.b, np.float64).copy()
            b[keep:] = 0.0
            state = dataclasses.replace(
                state, replicas=tu.tree_map(zero_tail, state.replicas), b=b
            )

        return self.resize(state, R - len(drop))

    def _remove_replicas_spanning(self, state, drop, merge_leavers):
        """Evict whole peer processes from a host-span fleet (DESIGN.md §10).

        The drop set must cover exact process blocks (the monitor emits
        process-grain events, so it always does); the local replica count
        is untouched — same mesh, same executor jit caches, zero
        recompiles. Every surviving process runs this identically:

        * final merge over survivors: the dead process can't contribute a
          partial, so the exchange's mass renormalization reproduces
          ``merge_leavers=False`` crash semantics exactly (with graceful
          leavers the peer is still exchanging and its updates fold in);
        * survivors-first renumbering is order-preserving, so each
          process's slot block stays contiguous; host-global vectors
          (b, lr, speed factors, virtual clocks) permute and shrink the
          same way the single-process path does.
        """
        span = self._span
        R = self.cfg.n_replicas
        victims = span.processes_for_slots(drop)
        self.invalidate_prefetch()

        alphas = np.asarray(state.b, np.float64).copy()
        if not merge_leavers:
            alphas[drop] = 0.0
        if alphas.sum() <= 0:
            alphas = np.ones(R, np.float64)
            if not merge_leavers:
                alphas[drop] = 0.0
        alphas = alphas / alphas.sum()
        merged, merged_replicas = self.merge_models(
            state.replicas, alphas, None, None, 0.0
        )

        dropset = set(drop)
        survivors = [i for i in range(R) if i not in dropset]
        perm = survivors + list(drop)
        if perm != list(range(R)):
            self.speed.permute(perm)
            self.scheduler.clock.permute(perm)
        new_R = R - len(drop)
        b_perm = np.asarray(state.b, np.float64)[perm]
        lr_perm = np.asarray(state.lr, np.float64)[perm]
        for pid in victims:
            span.remove_process(pid)
        self._adopt_width(new_R)
        new_cfg = self.cfg
        new_b, new_lr = self.algo.resize_b(
            new_cfg, b_perm[:new_R], lr_perm[:new_R], self.base_lr
        )

        policy = getattr(self.algo, "resize_policy", "merge")
        if policy == "merge":
            new_replicas = merged_replicas
            new_momentum = state.momentum  # survivors keep their momentum
        else:
            # 'preserve': survivors keep their own rows — which are exactly
            # the local rows this process already holds
            new_replicas = state.replicas
            new_momentum = state.momentum
        new_global = merged if state.global_model is not None else None
        new_prev = merged if state.prev_global is not None else None
        new_replicas, new_momentum, new_global, new_prev = self._place_state(
            new_replicas, new_momentum, new_global, new_prev
        )
        return ElasticState(
            replicas=new_replicas,
            global_model=new_global,
            prev_global=new_prev,
            momentum=new_momentum,
            b=np.asarray(new_b, np.float64),
            lr=np.asarray(new_lr, np.float64),
            megabatch_idx=state.megabatch_idx,
        )

    # ------------------------------------------------------------------
    # one mega-batch (DESIGN.md §1, §8)
    # ------------------------------------------------------------------
    def run_megabatch(
        self, state: ElasticState, prefetch: Optional[bool] = None
    ) -> tuple[ElasticState, dict]:
        """Plan, execute, and merge one mega-batch; returns (new_state, info).

        Generic engine sequence — every step delegates to the strategy:
        ``algo.plan`` → rounds (with ``algo.round_transforms`` traced in) →
        merge-cost accounting and ``algo.adapt`` → ``algo.merge``.

        The one mega-batch path (DESIGN.md §8): dispatch N from its staged
        plan (prefetched at the previous boundary, else staged now), then
        do the host work for N+1 (adapt → plan → fused pack → batched
        upload) *before* the single host sync that collects N's metrics —
        on an async backend the device is busy with N throughout.
        ``prefetch`` stages N+1 only with ``overlap`` on; it is opt-in
        (run() and bench loops pass it), so a bare call leaves no dangling
        staged plan and the caller's live cursors (provider / clock /
        speed) stay where one sequential mega-batch leaves them.

        Host-stateful operations keep the order … plan N → merge-cost clock
        bump N → plan N+1 … with or without prefetch, and ``merge``/
        ``adapt``/the guard are pure functions of (state, plan, device
        results), so trajectories are bit-identical either way under the
        simulated speed model. Under a measured speed model, a prefetched
        plan N+1 is made with factors one window stale (DESIGN.md §8).

        Donation contract: with the scan engine on TPU/GPU, ``state.replicas``
        and ``state.momentum`` are DONATED to the device program — treat
        ``state`` as consumed and continue from the returned state only.
        (On CPU donation is disabled and old states stay readable.)
        """
        self._current_megabatch = int(state.megabatch_idx)
        cfg = self.cfg
        R = cfg.n_replicas
        mb = int(state.megabatch_idx)
        staged = self._take_staged(state)
        if staged is None:
            staged = self._stage_megabatch(state.b, state.lr, mb)
        plan = staged.plan

        # measured-speed feedback (DESIGN.md §5): the window runs from
        # dispatch to the metrics collect, which brackets the device work
        measure = isinstance(self.speed, MeasuredSpeedModel)
        t_start = self.speed.begin() if measure else None
        if measure and self._shard_timer is not None:
            self._shard_timer.reset(int(self.mesh.shape[REPLICA_AXIS]))
        with trace_span("dispatch", megabatch=mb):
            if self.engine == "legacy_loop":
                replicas, momentum, m = self._run_rounds_legacy(state, staged)
            else:
                replicas, momentum, m = self._megabatch(
                    state.replicas,
                    state.momentum,
                    staged.batches,
                    staged.lr_dev,
                    staged.mask,
                    transforms=self._transforms,
                )

        # ---- host work overlapped with the in-flight device program ----
        with trace_span("adapt", megabatch=mb):
            # merge happens at the barrier and costs virtual time on every
            # replica; the strategy decides how many merges a mega-batch
            # incurs (per-round for eager synchronous schemes, once for
            # barrier-only)
            n_merges = self.algo.merges_per_megabatch(plan)
            self.scheduler.clock.t[:] += self.merge_cost * n_merges
            virtual_time = float(self.scheduler.clock.t.max())
            new_b, new_lr = self.algo.adapt(state, plan, cfg)
        if prefetch and self.overlap:
            self._staged = self._stage_megabatch(new_b, new_lr, mb + 1)

        # ---- collect: the single host sync of the mega-batch ----
        train_loss, train_acc = self._finish_metrics(m)
        # the staged slot's consumer is done on device -> reusable two
        # stagings from now (the other slot is next in line)
        if staged.slot_id is not None:
            self._staging.release(staged.slot_id)
        if measure:
            self._observe_window(plan, R, self.speed.elapsed(t_start))

        # ---- non-finite guard (DESIGN.md §7) ----
        # A replica whose params went NaN/Inf during the rounds is healed
        # *before* the barrier so it can never poison the merged global.
        # Detection is read-only: a healthy mega-batch is bit-identical
        # with the guard on or off.
        guard_repaired: list[int] = []
        if self.guard_nonfinite:
            finite = self._global_finite_rows(replicas)
            if not finite.all():
                replicas, momentum = self._repair_nonfinite(
                    state, replicas, momentum, finite
                )
                guard_repaired = np.flatnonzero(~finite).tolist()

        # ---- merge (the barrier) ----
        with trace_span("merge", megabatch=mb):
            outcome = self.algo.merge(self, state, plan, replicas)
        alphas = (
            outcome.alphas if outcome.alphas is not None else np.full(R, 1.0 / R)
        )

        new_state = ElasticState(
            replicas=outcome.replicas,
            global_model=outcome.global_model,
            prev_global=outcome.prev_global,
            momentum=momentum,
            b=np.asarray(new_b, np.float64),
            lr=np.asarray(new_lr, np.float64),
            megabatch_idx=state.megabatch_idx + 1,
        )
        info = {
            "n_replicas": R,
            "u": plan.u.tolist(),
            "b": np.round(np.asarray(new_b), 2).tolist(),
            "lr": np.round(np.asarray(new_lr), 6).tolist(),
            "alphas": np.round(np.asarray(alphas, np.float64), 4).tolist(),
            "pert_active": bool(outcome.pert_active),
            "train_loss": train_loss,
            "train_accuracy": train_acc,
            "virtual_time": virtual_time,
            "n_rounds": plan.n_rounds,
            **staged.counters,
        }
        if guard_repaired:
            info["guard_repaired"] = guard_repaired
        return new_state, info

    def _run_rounds_legacy(self, state: ElasticState, staged: _StagedMegaBatch):
        """The per-round host loop, the scan engine's test reference
        (DESIGN.md §1): one jitted ``round_body`` dispatch and one metrics
        read per staged round, bucket padding included. Returns what
        ``self._megabatch`` returns under a host span — the per-round raw
        sums, which ``_finish_metrics`` reduces with the in-program
        arithmetic."""
        replicas, momentum = state.replicas, state.momentum
        sums = []
        for i in range(staged.mask.shape[0]):
            mask = staged.mask[i]
            replicas, momentum, m = self._round(
                replicas, momentum, {k: v[i] for k, v in staged.batches.items()},
                staged.lr_dev, mask, transforms=self._transforms,
            )
            w = np.asarray(mask, np.float32)
            sums.append([
                np.sum(np.asarray(m[k], np.float32) * w)
                for k in ("loss", "accuracy", "n_valid")
            ] + [np.sum(w)])
        return replicas, momentum, {"round_sums": np.asarray(sums, np.float32)}

    def _bucket_rounds(self, plan) -> int:
        """Rounds staged for ``plan``: its round count, padded to a power
        of two with fully-masked rounds under ``round_bucket``."""
        n = _next_pow2(plan.n_rounds) if self.round_bucket else plan.n_rounds
        return max(n, 1)

    def _slot_counters(self, plan, n_rounds: int) -> dict:
        """What the device computes against what it holds, counted from the
        plan: ``sample_slots`` (rounds × R × b_max) against ``samples``, and
        for a provider with nnz slots (``max_nnz``) ``nnz_slots`` (samples ×
        max_nnz) against ``nnz``, the dispatches' work units (Σ min(nnz_i,
        max_nnz)). For a model with ``n_features``, ``w1_pad_rows``: the
        zero rows w1 is stored with beyond them (a constant).
        ``merge_flat_leaves``: the parameter leaves the merge kernel cannot
        read as a device stores them, and flattens first (the replica axis
        on the lanes; kernels/weighted_merge), for a device's replicas."""
        samples = sum(d.n_samples for d in plan.dispatches)
        out = {
            "sample_slots": n_rounds * self.cfg.n_replicas * self.cfg.b_max,
            "samples": samples,
        }
        max_nnz = getattr(self.provider, "max_nnz", None)
        if max_nnz is not None:
            out["nnz_slots"] = samples * max_nnz
            out["nnz"] = sum(d.work for d in plan.dispatches)
        if self._w1_pad_rows is not None:
            out["w1_pad_rows"] = self._w1_pad_rows
        r = self._mesh_width()
        if self.cfg.placement == "sharded":
            r //= self.mesh.shape[REPLICA_AXIS]
        out["merge_flat_leaves"] = flat_leaves(jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((r,) + l.shape, l.dtype),
            self._param_shapes,
        ))
        return out

    def _finish_metrics(self, m) -> tuple[float, float]:
        """Collect a mega-batch's (loss, accuracy) from the device metrics.

        The scan engine returns the fully-reduced scalars. The host-span
        executor returns raw per-round sums over the *local* replicas
        (``round_sums``); the exchange completes the population sum and the
        host mirrors the in-jit normalization arithmetic in float32 — the
        only cross-process difference from the in-mesh psum path is float
        reassociation. A dead peer contributes nothing: that mega-batch's
        metrics cover the survivors. The legacy engine returns the same
        raw sums over every replica."""
        with trace_span("sync.metrics", megabatch=self._current_megabatch):
            if "round_sums" not in m:
                return float(m["loss"]), float(m["accuracy"])
            sums = np.asarray(m["round_sums"], np.float32)
        if self._span is not None:
            total, _ = self._span.allreduce_sum("metrics", {"sums": sums})
            sums = np.asarray(total["sums"], np.float32)
        denom = np.maximum(sums[:, 3], np.float32(1.0))
        loss_r = sums[:, 0] / denom
        acc_r = sums[:, 1] / denom
        live = (sums[:, 3] > 0).astype(np.float32)
        n_live = np.maximum(live.sum(dtype=np.float32), np.float32(1.0))
        return (
            float(loss_r.sum(dtype=np.float32) / n_live),
            float(acc_r.sum(dtype=np.float32) / n_live),
        )

    def _observe_window(self, plan, R: int, seconds: float) -> None:
        """Feed one mega-batch's measurement window to the speed model:
        per-shard callback windows when the sharded executors produced a
        complete set, else the whole host window (legacy engine, vmap
        placement, or a marker lost in flight)."""
        windows = None
        if self._shard_timer is not None:
            jax.effects_barrier()   # debug callbacks are async; flush them
            windows = self._shard_timer.take()
        if windows is not None:
            self.speed.observe_shards(
                windows, plan.per_replica_work(R), u=plan.u,
                n_rounds=plan.n_rounds,
            )
        else:
            self.speed.observe_plan(
                plan.per_replica_work(R), seconds, u=plan.u,
                n_rounds=plan.n_rounds,
            )

    def _cursor_snapshot(self) -> dict:
        """Deep copies of every host cursor a staging plan advances:
        provider stream (sample RNG + position), virtual clocks, and — for
        the simulated model, whose planning consumes jitter RNG — the speed
        state. The measured model is not snapshotted: planning does not
        mutate it, and rolling it back would clobber window observations
        made after the snapshot."""
        return {
            "provider": (
                copy.deepcopy(self.provider.state_dict())
                if hasattr(self.provider, "state_dict") else None
            ),
            "clock_t": np.asarray(self.scheduler.clock.t, np.float64).copy(),
            "speed": (
                None if isinstance(self.speed, MeasuredSpeedModel)
                else copy.deepcopy(self.speed.state_dict())
            ),
        }

    def _stage_megabatch(
        self, b: np.ndarray, lr: np.ndarray, megabatch_idx: int
    ) -> _StagedMegaBatch:
        """Plan one mega-batch and stage it onto the devices.

        Fetches lazily where the provider supports it (ids + work units
        only), packs the whole plan grid in one fused vectorized gather into
        a double-buffered host slot, and issues a single batched
        ``jax.device_put`` of {batches, mask, lr} — onto the replica mesh
        under the sharded placement, so the executor's in_specs are already
        satisfied. The cursor snapshot is taken first, making the whole
        staging revocable (``invalidate_prefetch``) and checkpoint-safe
        (``checkpoint_payload``).
        """
        mb = int(megabatch_idx)
        with trace_span("stage", megabatch=mb):
            cfg = self.cfg
            R = cfg.n_replicas
            b_slots = cfg.b_max
            mega_samples = cfg.mega_batch * cfg.b_max
            b = np.asarray(b, np.float64).copy()
            lr = np.asarray(lr, np.float64).copy()
            snapshot = self._cursor_snapshot()

            provider = self.provider
            if hasattr(provider, "fetch_staged"):
                def fetch(i, take):
                    return provider.fetch_staged(take, b_slots)
            else:
                def fetch(i, take):
                    payload = provider.fetch(take, b_slots)
                    return payload, provider.work_units(payload)

            with trace_span("stage.plan", megabatch=mb):
                view = _PlanView(b=b, lr=lr, megabatch_idx=megabatch_idx)
                plan = self.algo.plan(self.scheduler, view, mega_samples, fetch)
                grid = plan.payload_grid(R, min_rounds=self._bucket_rounds(plan))

            with trace_span("stage.pack", megabatch=mb):
                slot_id, out = None, None
                if hasattr(provider, "staging_spec"):
                    spec = provider.staging_spec(len(grid), R, b_slots)
                    slot_id, out = self._staging.acquire(spec)
                    batches_np, mask = provider.stack_plan(grid, b_slots, out=out)
                else:
                    batches_np, mask = provider.stack_plan(grid, b_slots)

            lr32 = np.asarray(lr, np.float32)
            if self._span is not None:
                # host span: upload only this process's replica columns (the
                # staging slot still packs the full global grid — its shapes
                # key the double buffer; the slices below are views)
                sl = self._span_slice()
                batches_np = {k: v[:, sl] for k, v in batches_np.items()}
                mask = mask[:, sl]
                lr32 = lr32[sl]
            with trace_span("stage.upload", megabatch=mb):
                if cfg.placement == "sharded":
                    s1 = NamedSharding(self.mesh, replica_spec(1))
                    s0 = NamedSharding(self.mesh, replica_spec(0))
                    if self._global_put:
                        batches = {
                            k: self._put_leaf(v, s1) for k, v in batches_np.items()
                        }
                        mask_dev = self._put_leaf(mask, s1)
                        lr_dev = self._put_leaf(lr32, s0)
                    else:
                        batches, mask_dev, lr_dev = jax.device_put(
                            (batches_np, mask, lr32),
                            ({k: s1 for k in batches_np}, s1, s0),
                        )
                else:
                    batches, mask_dev, lr_dev = jax.device_put(
                        (batches_np, mask, lr32)
                    )
            return _StagedMegaBatch(
                plan=plan, batches=batches, mask=mask_dev, lr_dev=lr_dev,
                b=b, lr=lr, megabatch_idx=mb, n_replicas=R,
                slot_id=slot_id, snapshot=snapshot,
                counters=self._slot_counters(plan, len(grid)),
            )

    def _take_staged(self, state: ElasticState) -> Optional[_StagedMegaBatch]:
        """Consume the prefetched mega-batch if it matches ``state`` —
        same mega-batch index, population width, and b/lr vectors. Any
        mismatch (an out-of-band mutation that did not go through
        ``invalidate_prefetch``) discards it with a cursor rollback so the
        plan is simply replayed."""
        s = self._staged
        if s is None:
            return None
        self._staged = None
        if (
            s.megabatch_idx == int(state.megabatch_idx)
            and s.n_replicas == self.cfg.n_replicas
            and np.array_equal(s.b, np.asarray(state.b, np.float64))
            and np.array_equal(s.lr, np.asarray(state.lr, np.float64))
        ):
            return s
        self._discard_staged(s)
        return None

    def invalidate_prefetch(self) -> None:
        """Revoke the prefetched mega-batch (if any) and roll every host
        cursor back to the pre-staging snapshot. Called before anything
        that invalidates a staged plan — a resize, targeted eviction, fleet
        speed mutation, or checkpoint restore — so the next mega-batch
        replans from unconsumed cursors (correctness over overlap,
        DESIGN.md §8)."""
        s = self._staged
        if s is None:
            return
        self._staged = None
        self._discard_staged(s)

    def _discard_staged(self, s: _StagedMegaBatch) -> None:
        snap = s.snapshot
        if snap["provider"] is not None and hasattr(self.provider, "load_state_dict"):
            self.provider.load_state_dict(snap["provider"])
        self.scheduler.clock.t[:] = snap["clock_t"]
        if snap["speed"] is not None:
            self.speed.load_state_dict(snap["speed"])
        if s.slot_id is not None:
            self._staging.release(s.slot_id)

    def _global_finite_rows(self, replicas) -> np.ndarray:
        """(R,) bool over the *global* population. Host span: the local
        detection masks allgather so every process agrees on which rows
        need repair (and therefore issues the same repair exchanges); a
        dead peer's rows read finite — its weight is handled by eviction,
        not the guard."""
        with trace_span("sync.guard", megabatch=self._current_megabatch):
            finite_local = np.asarray(self._finite_rows(replicas), bool)
        if self._span is None:
            return finite_local
        span = self._span
        gathered = span.allgather("finite", finite_local)
        out = np.ones(self.cfg.n_replicas, bool)
        for pid, arr in gathered.items():
            plo, phi = span.bounds_of(pid)
            out[plo:phi] = np.asarray(arr, bool)
        return out

    def _repair_nonfinite(self, state, replicas, momentum, finite):
        """Re-clone non-finite replicas from a finite donor (DESIGN.md §7).

        The poisoned rows are zeroed first — a zero merge weight alone is
        not enough, ``0 * NaN`` is still NaN — then overwritten with the
        donor: the Algorithm-2 normalized merge of the *finite* rows
        (weights ``b_i`` restricted to them, so the poisoned replicas'
        weight is redistributed by the normalization). Since the donor
        carries exactly the survivors' relative weights, the algorithm's
        subsequent barrier merge over the repaired population equals the
        merge that would have excluded the poisoned rows outright. A fully
        diverged population (the sync family averages gradients *across*
        replicas each round, so one NaN reaches every row within the
        mega-batch) restarts from the last barrier global instead; an
        algorithm that keeps no global copy cannot recover and raises.
        Healed replicas continue with zeroed momentum and their b/lr
        untouched (Algorithm 1 adapts them onward as usual).

        Host span: ``finite`` is the exchange-agreed *global* mask; the
        row operations below apply its local slice, and the donor merge
        (span-aware ``merge_models``) runs on every process — identical
        global mask → identical exchange sequence.
        """
        mask = jnp.asarray(finite[self._span_slice()])

        def keep_rows(l, fill):
            m = mask.reshape((-1,) + (1,) * (l.ndim - 1))
            return jnp.where(m, l, fill)

        replicas = tu.tree_map(
            lambda l: keep_rows(l, jnp.zeros_like(l)), replicas
        )
        if finite.any():
            alphas = np.where(finite, np.asarray(state.b, np.float64), 0.0)
            donor, _ = self.merge_models(
                replicas, alphas / alphas.sum(), None, None, 0.0
            )
        elif state.global_model is not None:
            donor = state.global_model
        else:
            raise FloatingPointError(
                "all replicas diverged to non-finite values and algorithm "
                f"{self.algo.name!r} keeps no global model to restart from"
            )
        replicas = tu.tree_map(
            lambda l, g: keep_rows(
                l, jnp.broadcast_to(g[None].astype(l.dtype), l.shape)
            ),
            replicas,
            donor,
        )
        if momentum is not None:
            momentum = tu.tree_map(
                lambda l: keep_rows(l, jnp.zeros_like(l)), momentum
            )
        return replicas, momentum

    # ------------------------------------------------------------------
    # evaluation + full run
    # ------------------------------------------------------------------
    @staticmethod
    def _eval_cache_key(test_batches: list) -> tuple:
        """Content fingerprint of a test set: length plus the identities of
        the first/last payloads. List identity alone (the PR-3 cache key)
        went stale when a caller rebuilt the list object *or* mutated the
        same list in place — both now change the fingerprint. (A swap of
        only a middle element still aliases; callers doing surgical edits
        should pass a fresh list.)"""
        return (
            id(test_batches),
            len(test_batches),
            id(test_batches[0]) if test_batches else None,
            id(test_batches[-1]) if test_batches else None,
        )

    def _staged_test_batches(self, test_batches: list) -> list:
        """Stack + upload the test set once; reuse the device arrays.

        ``evaluate`` used to re-stack and re-upload every payload on every
        call — pure host overhead repeated each eval. The staged batches
        are cached by the content fingerprint above, so repeated
        evaluation of the same test set only runs the jitted loss while a
        rebuilt or mutated test set re-stages. The source list *and its
        current payloads* are kept referenced so none of the fingerprint
        ids can be recycled by new objects between calls.
        """
        key = self._eval_cache_key(test_batches)
        if self._eval_batches_key != key:
            staged = []
            for payload in test_batches:
                batch = {
                    k: jnp.asarray(v[0])
                    for k, v in self.provider.stack([payload]).items()
                }
                staged.append(batch)
            self._eval_batches = staged
            self._eval_batches_key = key
            self._eval_batches_src = (test_batches, list(test_batches))
        return self._eval_batches

    def evaluate_async(self, params: PyTree, test_batches: list):
        """Dispatch the jitted eval of every staged test batch without
        syncing; returns a zero-arg collector that blocks on the results.
        The overlap pipeline (DESIGN.md §8) dispatches at a mega-batch
        boundary and collects at the next one, so eval device work queues
        behind (and interleaves with) the next mega-batch instead of
        stalling the host between them."""
        mb = self._current_megabatch
        with trace_span("eval.dispatch", megabatch=mb):
            pending = [
                self._eval(params, batch)
                for batch in self._staged_test_batches(test_batches)
            ]

        def collect() -> dict:
            tot_acc, tot_loss, tot_n = 0.0, 0.0, 0.0
            with trace_span("sync.eval", megabatch=mb):
                for loss, aux in pending:
                    n = float(aux["n_valid"])
                    tot_acc += float(aux["accuracy"]) * n
                    tot_loss += float(loss) * n
                    tot_n += n
            return {
                "accuracy": tot_acc / max(tot_n, 1.0),
                "loss": tot_loss / max(tot_n, 1.0),
            }

        return collect

    def evaluate(self, params: PyTree, test_batches: list) -> dict:
        return self.evaluate_async(params, test_batches)()

    def _span_gather_state(self, state: ElasticState):
        """Assemble width-complete ``(replicas, momentum)`` host trees under
        a host span: allgather every live process's local rows and lay them
        into global-``R`` numpy arrays by slot block. Rows belonging to
        already-evicted processes no longer exist (the width shrank with
        them), so the only fill needed is for peers that die *during* this
        exchange — their rows take the global model broadcast (replicas) /
        zeros (momentum), matching what a crash eviction would have merged
        away anyway.
        """
        span = self._span
        R = int(self.cfg.n_replicas)
        reps_local = tu.tree_map(np.asarray, state.replicas)
        mom_local = (
            tu.tree_map(np.asarray, state.momentum)
            if state.momentum is not None else None
        )
        gathered = span.allgather(
            "ckpt", {"replicas": reps_local, "momentum": mom_local}
        )
        have = sorted(gathered)
        g_np = (
            tu.tree_map(np.asarray, state.global_model)
            if state.global_model is not None else None
        )

        def assemble(key: str, fill_tree):
            local_tree = gathered[span.process_id][key]
            if local_tree is None:
                return None
            local_leaves, treedef = jax.tree_util.tree_flatten(local_tree)
            by_pid = {
                pid: jax.tree_util.tree_flatten(gathered[pid][key])[0]
                for pid in have
            }
            fill_leaves = (
                jax.tree_util.tree_leaves(fill_tree)
                if fill_tree is not None else None
            )
            out = []
            for i, leaf in enumerate(local_leaves):
                g = np.zeros((R,) + leaf.shape[1:], leaf.dtype)
                if fill_leaves is not None:
                    g[:] = fill_leaves[i][None]
                for pid in have:
                    lo, hi = span.bounds_of(pid)
                    g[lo:hi] = by_pid[pid][i]
                out.append(g)
            return jax.tree_util.tree_unflatten(treedef, out)

        return assemble("replicas", g_np), assemble("momentum", None)

    # ------------------------------------------------------------------
    # crash-consistent checkpointing (DESIGN.md §7)
    # ------------------------------------------------------------------
    def checkpoint_payload(self, state: ElasticState) -> tuple[dict, dict]:
        """Everything a restored run needs to continue the exact
        trajectory: ``(tensor_tree, json_metadata)`` for
        ``checkpoint.store.save``. Tensors cover the model state (replicas,
        globals, momentum), the per-replica b/lr, the scheduler's virtual
        clocks, and the speed model's arrays; metadata carries the
        mega-batch index, population width, algorithm name, the speed
        model's counters/RNG, and the data provider's stream cursor + RNG.

        Prefetch interplay (DESIGN.md §8): when a mega-batch for this exact
        ``state`` is staged but unconsumed, the *snapshot* cursors from
        before its staging plan are checkpointed instead of the live ones —
        the prefetched batch has not been trained on, so a restore must
        replay it, not skip it. (Provider stream, virtual clocks, and the
        simulated speed model roll back; a measured model's EMAs are
        observation history, not plan cursors, and stay live.)
        """
        speed_sd = self.speed.state_dict()
        provider_sd = (
            self.provider.state_dict()
            if hasattr(self.provider, "state_dict") else None
        )
        clock_t = np.asarray(self.scheduler.clock.t, np.float64)
        staged = self._staged
        if staged is not None and staged.megabatch_idx == int(state.megabatch_idx):
            snap = staged.snapshot
            if snap["provider"] is not None:
                provider_sd = snap["provider"]
            clock_t = np.asarray(snap["clock_t"], np.float64)
            if snap["speed"] is not None:
                speed_sd = snap["speed"]
        replicas_ckpt, momentum_ckpt = state.replicas, state.momentum
        if self._span is not None:
            # width-complete checkpoint (DESIGN.md §10): allgather every
            # process's rows so a single-process run can restore it. Every
            # process assembles the payload (the allgather is an exchange —
            # all must participate on the deterministic interval), but only
            # the publishing manager writes (CheckpointManager(publisher=)).
            replicas_ckpt, momentum_ckpt = self._span_gather_state(state)
        tree = {
            "replicas": replicas_ckpt,
            "momentum": momentum_ckpt,
            "global_model": state.global_model,
            "prev_global": state.prev_global,
            "b": np.asarray(state.b, np.float64),
            "lr": np.asarray(state.lr, np.float64),
            "clock_t": clock_t,
            "speed": speed_sd["arrays"],
        }
        metadata = {
            "format": 1,
            "megabatch_idx": int(state.megabatch_idx),
            "n_replicas": int(self.cfg.n_replicas),
            "algorithm": self.cfg.algorithm,
            "seed": int(self.seed),
            "has": {
                "momentum": state.momentum is not None,
                "global_model": state.global_model is not None,
                "prev_global": state.prev_global is not None,
            },
            "speed_meta": speed_sd["meta"],
        }
        if provider_sd is not None:
            metadata["provider"] = provider_sd
        return tree, metadata

    def restore_checkpoint(self, path: str) -> ElasticState:
        """Rebuild the full training state from an atomic checkpoint.

        ``path`` is one checkpoint directory or a manager directory (the
        newest complete checkpoint is taken). The trainer must be
        constructed with the same model/algorithm/config family as the
        writer — structural mismatches raise
        :class:`repro.checkpoint.store.CheckpointError` — but its
        construction-time replica count may differ: the checkpointed width
        is adopted (``_adopt_width``), exactly like a resize to it.
        """
        from repro.checkpoint import store as ckpt_store

        # any prefetched plan belongs to the pre-restore trajectory
        self.invalidate_prefetch()
        path = ckpt_store.resolve_checkpoint(path)
        meta = ckpt_store.load_metadata(path)
        if meta.get("algorithm") != self.cfg.algorithm:
            raise ckpt_store.CheckpointError(
                f"checkpoint {path} was written by algorithm "
                f"{meta.get('algorithm')!r}; this trainer runs "
                f"{self.cfg.algorithm!r}"
            )
        new_R = int(meta["n_replicas"])
        if new_R != self.cfg.n_replicas:
            if self._span is not None:
                # re-split the checkpointed global width across the live
                # processes before adopting it (raises if indivisible)
                self._span.assign_slots(new_R)
            self._adopt_width(new_R)
        speed_sd = self.speed.state_dict()
        ckpt_kind = meta.get("speed_meta", {}).get("kind")
        if ckpt_kind != speed_sd["meta"]["kind"]:
            raise ckpt_store.CheckpointError(
                f"checkpoint {path} carries a {ckpt_kind!r} speed model; "
                f"this trainer uses {speed_sd['meta']['kind']!r}"
            )
        ref = self.init_state()
        has = meta.get("has", {})
        if bool(has.get("momentum")) != (ref.momentum is not None):
            raise ckpt_store.CheckpointError(
                f"checkpoint {path} "
                f"{'has' if has.get('momentum') else 'lacks'} momentum but "
                "this trainer's SGD config disagrees"
            )
        # global/prev presence follows the *checkpoint*, not init_state:
        # algorithms without Alg.-2 global copies still publish a global
        # model from their first barrier onward (MergeOutcome.global_model)
        params_like = tu.tree_replica_slice(ref.replicas, 0)
        like_replicas, like_momentum = ref.replicas, ref.momentum
        if self._span is not None:
            # checkpoints are width-complete (global R); the local ref trees
            # only span this process's block, so rebuild global-width likes
            like_replicas = tu.tree_broadcast_replicas(params_like, new_R)
            if ref.momentum is not None:
                like_momentum = tu.tree_map(
                    lambda l: jnp.zeros((new_R,) + l.shape[1:], l.dtype),
                    ref.momentum,
                )
        like = {
            "replicas": like_replicas,
            "momentum": like_momentum,
            "global_model": params_like if has.get("global_model") else None,
            "prev_global": params_like if has.get("prev_global") else None,
            "b": np.zeros(new_R, np.float64),
            "lr": np.zeros(new_R, np.float64),
            "clock_t": np.zeros(new_R, np.float64),
            "speed": speed_sd["arrays"],
        }
        tree, _ = ckpt_store.load(path, like)
        self.scheduler.clock.t[:] = np.asarray(tree["clock_t"], np.float64)
        self.speed.load_state_dict(
            {"arrays": tree["speed"], "meta": meta["speed_meta"]}
        )
        if isinstance(self.speed, MeasuredSpeedModel):
            # the fresh process jit-compiles inside the first timed window
            self.speed.discard_next_window()
        if "provider" in meta and hasattr(self.provider, "load_state_dict"):
            self.provider.load_state_dict(meta["provider"])
        replicas_t, momentum_t = tree["replicas"], tree["momentum"]
        if self._span is not None:
            # keep only this process's slot block of the global-width rows
            sl = self._span_slice()
            replicas_t = tu.tree_map(lambda l: np.asarray(l)[sl], replicas_t)
            if momentum_t is not None:
                momentum_t = tu.tree_map(
                    lambda l: np.asarray(l)[sl], momentum_t
                )
        replicas, momentum, global_model, prev_global = self._place_state(
            replicas_t, momentum_t,
            tree["global_model"], tree["prev_global"],
        )
        return ElasticState(
            replicas=replicas,
            global_model=global_model,
            prev_global=prev_global,
            momentum=momentum,
            b=np.asarray(tree["b"], np.float64),
            lr=np.asarray(tree["lr"], np.float64),
            megabatch_idx=int(meta["megabatch_idx"]),
        )

    def _validate_resize_schedule(
        self, resize_schedule: dict
    ) -> dict[int, int]:
        """Normalize + validate a resize schedule at launch (DESIGN.md §6).

        Rejects negative mega-batch indices, entries that collide after int
        normalization (``{"3": 4, 3: 6}``), and replica targets the
        algorithm's resize_policy would refuse 40 mega-batches in — a bad
        ``--elastic-schedule`` must fail before training starts.
        """
        out: dict[int, int] = {}
        policy = getattr(self.algo, "resize_policy", "merge")
        for raw_mb, raw_R in resize_schedule.items():
            mb, target = int(raw_mb), int(raw_R)
            if mb != float(raw_mb) or target != float(raw_R):
                raise ValueError(
                    f"resize schedule entry {raw_mb!r}: {raw_R!r} is not "
                    "an integer pair"
                )
            if mb < 0:
                raise ValueError(
                    f"resize schedule has negative mega-batch index {mb}"
                )
            if mb in out:
                raise ValueError(
                    f"resize schedule defines mega-batch {mb} twice "
                    "(duplicate after normalization)"
                )
            resolved = int(self.algo.resolve_n_replicas(target))
            if resolved < 1:
                raise ValueError(
                    f"resize schedule targets {target} replicas at "
                    f"mega-batch {mb}"
                )
            if policy == "fixed" and resolved != self.cfg.n_replicas:
                raise ValueError(
                    f"algorithm {self.algo.name!r} pins its replica "
                    f"membership (resize_policy='fixed'); schedule entry "
                    f"{mb}: {target} is invalid"
                )
            out[mb] = target
        return out

    def run(
        self,
        n_megabatches: int,
        test_batches: Optional[list] = None,
        eval_every: int = 1,
        verbose: bool = False,
        resize_schedule: Optional[dict[int, int]] = None,
        fleet: Optional[Any] = None,
        checkpoint: Optional[Any] = None,
        restore_from: Optional[str] = None,
    ) -> tuple[ElasticState, MetricsLog]:
        """Train ``n_megabatches`` mega-batches.

        ``resize_schedule`` maps a 0-based mega-batch index to the replica
        count that takes effect *before* that mega-batch runs (the
        launcher's ``--elastic-schedule "0:4,20:6,40:3"``): workers join or
        leave at those boundaries via ``resize``. An entry matching the
        current R is a no-op, so a constant schedule reproduces the
        unscheduled run bit-for-bit. Schedules are validated up front.

        ``fleet`` — a ``core.fleet.FleetController``: reactive membership.
        Its ``step(trainer, state, mb)`` runs at each boundary (after any
        scheduled resize), consuming fault events and health signals.

        ``checkpoint`` — a ``checkpoint.store.CheckpointManager``: after
        every mega-batch ``maybe_save`` snapshots on its interval; the
        final in-flight write is joined before returning.

        ``restore_from`` — checkpoint path (or manager directory): resume
        from it instead of ``init_state``. Training continues at the
        checkpointed mega-batch index; metrics of earlier mega-batches
        belong to the previous process's log.
        """
        if resize_schedule is not None:
            resize_schedule = self._validate_resize_schedule(resize_schedule)
        if restore_from is not None:
            state = self.restore_checkpoint(restore_from)
        else:
            state = self.init_state()
        mlog = MetricsLog()
        pending_eval = None  # (mlog record to backfill, collector)

        def emit_line(record):
            if not verbose:
                return
            log(
                f"[{self.cfg.algorithm}] mb={record['megabatch']}",
                loss=round(record["train_loss"], 4),
                acc=round(record.get("accuracy", float("nan")), 4),
                u=record["u"],
                b=record["b"],
                vt=round(record["virtual_time"], 3),
            )

        def drain_eval():
            nonlocal pending_eval
            if pending_eval is not None:
                record, collect = pending_eval
                ev = collect()
                record.update(accuracy=ev["accuracy"], test_loss=ev["loss"])
                pending_eval = None
                # the progress line for an async-eval boundary waits for the
                # backfill, so it never shows a placeholder accuracy
                emit_line(record)

        t0 = time.perf_counter()
        for mb in range(int(state.megabatch_idx), n_megabatches):
            if resize_schedule is not None and mb in resize_schedule:
                with trace_span("resize", megabatch=mb):
                    state = self.resize(state, resize_schedule[mb])
            if fleet is not None:
                with trace_span("fleet", megabatch=mb):
                    state = fleet.step(self, state, mb)
            # the final mega-batch stages nothing: run() must end with every
            # host cursor consumed (no dangling prefetch in checkpoints or
            # for a caller that continues this trainer by hand)
            state, info = self.run_megabatch(
                state, prefetch=mb + 1 < n_megabatches
            )
            if checkpoint is not None:
                with trace_span("checkpoint", megabatch=mb):
                    checkpoint.maybe_save(self, state)
            # collect the PREVIOUS boundary's async eval only now — its
            # device work ran behind this mega-batch instead of serializing
            drain_eval()
            collect = None
            if test_batches is not None and (mb + 1) % eval_every == 0:
                if self.overlap:
                    collect = self.evaluate_async(
                        state.global_model, test_batches
                    )
                else:
                    ev = self.evaluate(state.global_model, test_batches)
                    info.update(accuracy=ev["accuracy"], test_loss=ev["loss"])
            info["megabatch"] = mb + 1
            info["wall_clock"] = time.perf_counter() - t0
            mlog.append(**info)
            if collect is not None:
                # MetricsLog.append copies kv: backfill the stored record
                pending_eval = (mlog.records[-1], collect)
            else:
                emit_line(mlog.records[-1])
        drain_eval()
        if checkpoint is not None:
            checkpoint.wait()
        return state, mlog
