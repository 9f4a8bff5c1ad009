"""Gossip (consensus) step of DPASGD as TPU collective schedules.

The consensus matrix A (doubly stochastic, support = overlay edges) is
compiled to one of three implementations:

* ``einsum``   — w <- einsum('ij,j...->i...', A, w) over the leading silo
                 dimension.  Reference semantics; XLA lowers it to an
                 all-gather over the silo axis (cost independent of the
                 overlay sparsity — this is the *naive* schedule).
* ``ppermute`` — Birkhoff-von Neumann decomposition of A into
                 permutations; each permutation becomes one
                 ``jax.lax.ppermute`` inside a ``shard_map`` over the silo
                 axis.  Communication volume = (#non-identity permutations)
                 x |params| — proportional to the overlay degree, exactly
                 the dependence the paper's delay model (Eq. 3) rewards.
                 RING topologies need a single ppermute.
* ``pallas``   — same transfers as ``ppermute`` but the K-way weighted
                 combine runs through the fused ``gossip_mix`` kernel.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.birkhoff import birkhoff_decomposition
from repro.obs import metrics as obs_metrics

@dataclass(frozen=True)
class GossipPlan:
    """Compiled consensus schedule: one overlay's mixing as collectives.

    Attributes
    ----------
    matrix:
        ``[n, n]`` doubly-stochastic consensus matrix A (support = the
        overlay's arcs + self loops).
    terms:
        The Birkhoff decomposition of A as ``(coeff, perm)`` pairs, where
        ``perm[i]`` is the silo that destination i *receives from*; each
        non-identity term lowers to one ``jax.lax.ppermute``.
    n_silos:
        n, the silo count (== the silo mesh-axis size at runtime).
    """

    matrix: np.ndarray                       # [n, n] doubly stochastic
    terms: Tuple[Tuple[float, Tuple[int, ...]], ...]  # (coeff, recv-from perm)
    n_silos: int

    @staticmethod
    def from_matrix(A: np.ndarray) -> "GossipPlan":
        """Decompose a doubly-stochastic ``[n, n]`` matrix into a plan."""
        terms = birkhoff_decomposition(np.asarray(A, np.float64))
        packed = tuple((float(c), tuple(int(x) for x in p)) for c, p in terms)
        return GossipPlan(matrix=np.asarray(A), terms=packed, n_silos=A.shape[0])

    @property
    def num_transfers(self) -> int:
        """Non-identity permutations = point-to-point transfers per round."""
        ident = tuple(range(self.n_silos))
        return sum(1 for (_, p) in self.terms if p != ident)


class PlanSlot:
    """Hot-swap hook for the active gossip plan.

    A ``GossipPlan`` is baked into the jitted train step (its Birkhoff
    terms decide which ``ppermute`` calls are traced), so it cannot change
    under a compiled function's feet.  The slot makes the swap explicit:
    the training loop builds its step from ``slot.plan`` and re-lowers
    whenever ``slot.version`` moves; an online controller (see
    :mod:`repro.dynamics.controller`) calls :meth:`swap` between rounds.
    ``on_swap`` callbacks fire synchronously inside :meth:`swap` — e.g. to
    drop a cached compiled step.  ``history`` keeps an audit trail of
    (version, label) swaps.
    """

    _slot_kind = "plan"  # metric namespace; subclasses override

    def __init__(self, plan: GossipPlan):
        self._plan = plan
        self.version = 0
        self.history: List[Tuple[int, str]] = [(0, "init")]
        self._callbacks: List[Any] = []

    @property
    def plan(self) -> GossipPlan:
        return self._plan

    def on_swap(self, callback) -> Any:
        """Register ``callback(plan, version)``; returns it (decorator use)."""
        self._callbacks.append(callback)
        return callback

    def swap(self, plan: GossipPlan, label: str = "", *,
             allow_resize: bool = False) -> int:
        """Install ``plan`` and bump ``version``.

        A plan over a different silo count is rejected unless
        ``allow_resize=True`` — the caller asserting that the silo mesh
        axis is being rebuilt too (elastic membership: the controller
        resizes the plan only after swapping a
        :class:`MembershipSlot`, and the training loop migrates
        mesh/state before re-lowering on the resized plan)."""
        if not allow_resize and plan.n_silos != self._plan.n_silos:
            raise ValueError(
                f"plan spans {plan.n_silos} silos, slot holds {self._plan.n_silos}"
            )
        self._plan = plan
        self.version += 1
        self.history.append((self.version, label))
        obs_metrics.counter(f"slot.{self._slot_kind}_swaps").inc()
        obs_metrics.gauge(f"slot.{self._slot_kind}_version").set(self.version)
        for cb in self._callbacks:
            cb(plan, self.version)
        return self.version


class ScheduleSlot(PlanSlot):
    """Hot-swap slot for *schedule*-valued state (randomized plans).

    Extends :class:`PlanSlot` from one fixed :class:`GossipPlan` to a
    :class:`repro.core.schedule.Schedule`: every communication round the
    active schedule samples that round's overlay
    (``schedule.round_edges(k)``) and the slot materializes it as a
    consensus matrix / :class:`GossipPlan`.  Because ``round_edges`` is a
    pure function of (schedule state, round counter), **every silo
    holding an equal slot derives the identical plan for round k from the
    shared round counter alone** — no cross-silo coordination, the
    property MATCHA deployments rely on (Appendix G.3) and that
    ``tests/test_schedule.py`` pins down.

    Plans are cached per sampled edge set, bounded FIFO at
    ``max_cached_plans`` (a MATCHA schedule over few matchings revisits a
    small subset family; over many matchings almost every round is fresh
    and an unbounded cache would grow for the process lifetime), and
    ``version`` moves only on :meth:`swap_schedule` — per-round sampling
    is expected churn, not a topology change.  For a deterministic
    :class:`FixedSchedule` the slot degenerates to a :class:`PlanSlot`
    whose plan never varies.
    """

    _slot_kind = "schedule"

    def __init__(self, schedule, n_silos: int, silos: Optional[Sequence] = None,
                 max_cached_plans: int = 512):
        from repro.core.consensus import local_degree_matrix

        self._local_degree_matrix = local_degree_matrix
        self._n = int(n_silos)
        self._silos = tuple(silos) if silos is not None else None
        self._schedule = schedule
        self._plan_cache: dict = {}
        self._max_cached = int(max_cached_plans)
        super().__init__(self.plan_for_round(0))

    @property
    def schedule(self):
        return self._schedule

    def swap_schedule(self, schedule, label: str = "",
                      silos: Optional[Sequence] = None) -> int:
        """Install a new schedule (fixed or randomized); bumps ``version``
        and fires the ``on_swap`` callbacks with the round-0 plan.

        ``silos`` re-pins the label -> mesh-position order — pass it when
        elastic membership changed the active universe (the new schedule
        spans different silos than the old one); the round-0 plan is then
        allowed to change silo count, and the caller must rebuild the
        mesh/state to match (see :class:`MembershipSlot`)."""
        resized = silos is not None
        rollback = (self._schedule, self._silos, self._n, self._plan_cache,
                    self._plan, self.version, list(self.history))
        if resized:
            self._silos = tuple(silos)
            self._n = len(self._silos)
        self._schedule = schedule
        self._plan_cache = {}
        try:
            return self.swap(self.plan_for_round(0), label=label,
                             allow_resize=resized)
        except Exception:
            # failed swaps leave the slot untouched (PlanSlot invariant) —
            # including the base-class plan/version/history, which a
            # raising on_swap callback would otherwise leave half-moved
            (self._schedule, self._silos, self._n, self._plan_cache,
             self._plan, self.version, history) = rollback
            self.history[:] = history
            raise

    def _index(self, label) -> int:
        if self._silos is not None:
            return self._silos.index(label)
        return int(label)

    def plan_for_round(self, round_idx: int) -> GossipPlan:
        """The (deterministic) gossip plan of communication round
        ``round_idx`` under the active schedule."""
        edges = self._schedule.round_edges(round_idx)
        idx_edges = tuple(
            sorted(
                (self._index(i), self._index(j)) for (i, j) in edges if i != j
            )
        )
        plan = self._plan_cache.get(idx_edges)
        if plan is None:
            A = self._local_degree_matrix(self._n, list(idx_edges))
            plan = GossipPlan.from_matrix(A)
            if len(self._plan_cache) >= self._max_cached:  # FIFO bound
                self._plan_cache.pop(next(iter(self._plan_cache)))
            self._plan_cache[idx_edges] = plan
        return plan

    def matrix_for_round(self, round_idx: int) -> np.ndarray:
        """Consensus matrix of round ``round_idx`` — the array fed to a
        traced-consensus train step (no re-lowering between rounds)."""
        return self.plan_for_round(round_idx).matrix


class MembershipSlot:
    """Versioned active-silo set — the elastic-membership sibling of
    :class:`PlanSlot` / :class:`ScheduleSlot`.

    The silo *universe* (labels ``0..n_universe-1``, the underlay's full
    silo set) is fixed at launch; the *active* subset changes on
    ``SiloJoin`` / ``SiloLeave`` churn.  The device mesh axis and the
    silo-stacked train state are sized to ``active``, so unlike a plan
    swap a membership swap cannot be absorbed by re-lowering alone: the
    training loop watches ``version`` and on a move re-builds the mesh,
    migrates the state (gather → re-stack → re-shard; survivors keep
    their rows bit-identical, joiners enter at the survivors' consensus
    average — :func:`repro.fed.dpasgd.migrate_silo_state`), and re-lowers
    the train step over the new silo count.  The online controller calls
    :meth:`swap` when its membership signal drifts, *before* resizing the
    plan/schedule slots, so consumers always observe membership first.

    ``swap`` with an unchanged active set is a no-op (version does not
    move); ``history`` keeps the (version, label) audit trail and
    ``on_swap`` callbacks fire synchronously with ``(active, version)``.
    """

    def __init__(self, active: Sequence[int], n_universe: int):
        self._universe = int(n_universe)
        self._active = self._validate(active)
        self.version = 0
        self.history: List[Tuple[int, str]] = [(0, "init")]
        self._callbacks: List[Any] = []

    def _validate(self, active: Sequence[int]) -> Tuple[int, ...]:
        act = tuple(sorted(int(v) for v in active))
        if not act:
            raise ValueError("membership cannot be empty: >= 1 active silo")
        if len(set(act)) != len(act):
            raise ValueError(f"duplicate silos in membership {act}")
        if act[0] < 0 or act[-1] >= self._universe:
            raise ValueError(
                f"membership {act} outside universe 0..{self._universe - 1}"
            )
        return act

    @property
    def active(self) -> Tuple[int, ...]:
        """Sorted active silo labels; index k is mesh position k."""
        return self._active

    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def n_universe(self) -> int:
        return self._universe

    def on_swap(self, callback) -> Any:
        """Register ``callback(active, version)``; returns it."""
        self._callbacks.append(callback)
        return callback

    def swap(self, active: Sequence[int], label: str = "") -> int:
        """Install a new active set; returns the (possibly unmoved)
        version.  No-op when the set is unchanged."""
        act = self._validate(active)
        if act == self._active:
            return self.version
        self._active = act
        self.version += 1
        self.history.append((self.version, label))
        obs_metrics.counter("slot.membership_swaps").inc()
        obs_metrics.gauge("slot.membership_version").set(self.version)
        obs_metrics.gauge("slot.membership_active").set(len(act))
        for cb in self._callbacks:
            cb(act, self.version)
        return self.version


def gossip_einsum(params: Any, A: jax.Array) -> Any:
    """Reference gossip: dense mixing over the leading silo dimension.

    ``params`` is a pytree whose leaves carry a leading silo dim of size
    n; ``A`` is the ``[n, n]`` consensus matrix.  Returns the same pytree
    with every leaf replaced by ``einsum('ij,j...->i...', A, leaf)`` —
    XLA lowers this to an all-gather over the silo axis, so its traffic
    is overlay-independent (the naive baseline the ppermute schedule
    beats).  Its ops run under the ``gossip`` scope."""
    with jax.named_scope("gossip"):
        return jax.tree_util.tree_map(
            lambda w: jnp.einsum("ij,j...->i...", A.astype(w.dtype), w), params
        )


def _perm_to_pairs(perm: Sequence[int]) -> List[Tuple[int, int]]:
    """perm[i] = source silo for destination i -> ppermute (src, dst) pairs."""
    return [(int(s), int(d)) for d, s in enumerate(perm)]


def gossip_shard_map(
    params: Any,
    plan: GossipPlan,
    mesh: jax.sharding.Mesh,
    axis: str,
    *,
    use_pallas: bool = False,
    pallas_interpret: Optional[bool] = None,  # None = auto (TPU compiled)
    extra_spec: Tuple = (),
) -> Any:
    """Apply the Birkhoff ppermute schedule over mesh axis ``axis``.

    ``params`` leaves have a leading silo dim of size n_silos sharded over
    ``axis`` (plus whatever ``extra_spec`` shards the remaining dims).
    The transfers and the mix run under the ``gossip`` scope.
    """
    ident = tuple(range(plan.n_silos))

    def local_mix(w):
        # inside shard_map: w has leading silo dim of local size 1
        acc = None
        for (coeff, perm) in plan.terms:
            if perm == ident:
                contrib = coeff * w.astype(jnp.float32)
            else:
                recv = jax.lax.ppermute(w, axis, _perm_to_pairs(perm))
                contrib = coeff * recv.astype(jnp.float32)
            acc = contrib if acc is None else acc + contrib
        return acc.astype(w.dtype)

    def mix_tree(tree):
        if use_pallas:
            return _pallas_mix_tree(tree, plan, axis, interpret=pallas_interpret)
        return jax.tree_util.tree_map(local_mix, tree)

    spec = P(axis, *extra_spec) if extra_spec else P(axis)
    # Build per-leaf specs preserving each leaf's rank.
    leaves, treedef = jax.tree_util.tree_flatten(params)
    specs = [P(axis, *([None] * (l.ndim - 1))) for l in leaves]
    in_spec = jax.tree_util.tree_unflatten(treedef, specs)
    fn = jax.shard_map(mix_tree, mesh=mesh, in_specs=(in_spec,),
                       out_specs=in_spec, check_vma=False)
    with jax.named_scope("gossip"):
        return fn(params)


def _pallas_mix_tree(
    tree: Any, plan: GossipPlan, axis: str, *, interpret: Optional[bool] = None
) -> Any:
    """Gather neighbour copies via ppermute, then run the fused Pallas
    K-way combine over the flattened parameter vector.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter on CPU.
    """
    from repro.kernels import ops as kops

    ident = tuple(range(plan.n_silos))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1) for l in leaves])
    stack = []
    weights = []
    for (coeff, perm) in plan.terms:
        if perm == ident:
            stack.append(flat)
        else:
            stack.append(jax.lax.ppermute(flat, axis, _perm_to_pairs(perm)))
        weights.append(coeff)
    mixed = kops.gossip_mix(jnp.stack(stack), jnp.asarray(weights, jnp.float32),
                            interpret=interpret)
    out = []
    offset = 0
    for shape, size in zip(shapes, sizes):
        out.append(mixed[offset : offset + size].reshape(shape))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)


def collective_bytes_per_round(plan: GossipPlan, param_bytes: int) -> int:
    """Predicted gossip traffic per communication round per silo, to
    set against the compiled step's count
    (``launch/hlo_analysis.collective_bytes``)."""
    return plan.num_transfers * param_bytes
