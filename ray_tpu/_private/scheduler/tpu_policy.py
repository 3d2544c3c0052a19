"""TPU-accelerated scheduling policy — the north-star component.

Reference: the raylet scheduling hot loop ``ClusterResourceScheduler::
GetBestSchedulableNode`` → ``HybridSchedulingPolicy::Schedule``
(royf/ray ``src/ray/raylet/scheduling/policy/hybrid_scheduling_policy.cc``
[UNVERIFIED — mount empty, SURVEY.md §0]), which scans nodes per task in
scalar C++: O(pending × nodes) sequential work.

The TPU redesign (BASELINE.json:5) makes three structural moves instead
of translating that loop:

1. **Scheduling classes.** The pending queue is grouped by distinct
   (demand vector, preferred node) — the reference raylet itself keys
   its queues by "scheduling class", so a huge pending queue collapses
   to a handful of classes. 1M identical pi-tasks are ONE class.

2. **Class-level vectorized fill.** For one class, scheduling `count`
   tasks sequentially under the hybrid policy is equivalent to:
   pack the preferred node until the spread threshold, then fill the
   remaining nodes in least-critical-utilization order up to their
   per-node capacity ``cap[n] = floor(min_r avail[n,r]/demand[r])``.
   That whole fill is one fused device program: a [nodes, resources]
   elementwise block (VPU), an argsort by score, and a cumsum — no
   per-task work at all.

3. **Sequential-commit across classes via lax.scan.** Classes are
   scanned in order carrying the availability matrix, so a batch with
   mixed shapes never oversubscribes a node.

Per-task results are recovered on the host by expanding per-node counts
(np.repeat over the score order) — O(batch) numpy, off the device.

The policy registers as ``"tpu"`` in the ISchedulingPolicy registry and
is selected by ``use_tpu_scheduler`` (config) — the seam mandated by
BASELINE.json:5. The device-resident resource matrix is cached and
invalidated by ``ClusterResourceManager.version()``.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu._private.config import get_config
from ray_tpu._private.ids import NodeID
from ray_tpu._private.scheduler.policy import (
    ISchedulingPolicy,
    SchedulingRequest,
    SchedulingResult,
    register_policy,
)
from ray_tpu._private.scheduler.resources import ClusterResourceManager

logger = logging.getLogger(__name__)

_EPS = 1e-6


def _bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two ≥ n (≥ minimum) — keeps jit cache keys few."""
    b = minimum
    while b < n:
        b *= 2
    return b


# --------------------------------------------------------------------------
# The device kernel
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_classes",), donate_argnums=(0,))
def _schedule_classes_kernel(
    avail: jax.Array,        # [N, R] float32 — mutable availability view
    total: jax.Array,        # [N, R] float32
    alive: jax.Array,        # [N] bool
    demands: jax.Array,      # [K, R] float32 — per-class demand vector
    counts: jax.Array,       # [K] int32 — tasks in each class (0 = pad)
    prefs: jax.Array,        # [K] int32 — preferred node index, -1 = none
    threshold: jax.Array,    # scalar float32 — spread threshold
    num_classes: int,
):
    """Schedule K classes of tasks against N nodes in one device program.

    Three admission stages (docs/scheduler.md):

    1. **Feasibility fence.** Per class, the capacity bound from node
       TOTALS — ``sum_n floor(min_r total[n,r]/demand[r])`` over
       feasible nodes — caps how many instances the cluster could hold
       even when idle. Surplus beyond it is *fenced* out before
       scoring: the fill never attempts it, and the count is reported
       so the host can park the class (typed) instead of rescanning it
       every tick.
    2. **Scarcity-ordered commit.** Classes commit in descending order
       of their scarcest demanded resource's pressure
       (class-demand-weighted total demand / live supply), so
       abundant-resource classes cannot strand scarce (TPU) capacity
       ahead of the classes that need it. Outputs are returned in the
       caller's class order.
    3. **Residual fill.** A second fill pass (``lax.cond``-gated, so
       it costs nothing when the first pass placed everything it
       admitted) re-runs the water-fill over each class's unplaced
       admitted remainder against the post-commit availability — the
       backstop that keeps "every capacity-feasible task lands" an
       invariant rather than a proof obligation on the fp-exactness of
       the bisection fill.

    Returns (per-class, caller's order):
      local_take  [K]      — tasks packed onto the preferred node
      any_feasible[K]      — some alive node could EVER run the class
      fenced      [K]      — surplus beyond the totals capacity bound
      admitted    [K]      — min(count - fenced, live capacity at the
                             class's commit turn): what could place NOW
      order       [K, N]   — node indices in fill order (post-local)
      take_sorted [K, N]   — tasks given to order[k, j]
      order2/take2[K, N]   — residual-pass placements (zeros when the
                             residual pass did not run)
      new_avail   [N, R]
    """
    n_nodes = avail.shape[0]
    countsf = counts.astype(jnp.float32)

    # ---- scarcity ordering: commit scarce-resource classes first ----
    # Primary key: RARITY of the class's scarcest demanded resource —
    # the fraction of alive nodes whose totals carry it at all. A class
    # demanding a resource that lives on few nodes (TPU, custom) must
    # commit before abundant-resource classes eat those nodes'
    # complementary capacity (CPU/memory) and strand it; rarity is
    # count-independent, so an over-subscribed abundant resource can't
    # jump the queue. Secondary key: demand pressure (class-weighted
    # total demand / live supply), descending — among equally-rare
    # classes the most contended commits first.
    has_d = demands > 0.0                                            # [K, R]
    n_alive = jnp.maximum(jnp.sum(alive.astype(jnp.float32)), 1.0)
    res_frac = (jnp.sum((total > 0.0) & alive[:, None], axis=0)
                .astype(jnp.float32) / n_alive)                      # [R]
    rarity = jnp.min(jnp.where(has_d, res_frac[None, :], jnp.inf),
                     axis=1)                                         # [K]
    supply = jnp.sum(jnp.where(alive[:, None], avail, 0.0), axis=0)  # [R]
    class_demand = countsf[:, None] * demands                        # [K, R]
    pressure = jnp.sum(class_demand, axis=0) / jnp.maximum(supply, _EPS)
    press_k = jnp.max(jnp.where(has_d, pressure[None, :], -jnp.inf),
                      axis=1)                                        # [K]
    rarity = jnp.where(counts > 0, rarity, jnp.inf)       # pads last
    press_k = jnp.where(counts > 0, press_k, -jnp.inf)
    perm = jnp.lexsort((-press_k, rarity))    # rarity asc, pressure desc
    inv = jnp.argsort(perm)
    demands_c = demands[perm]
    counts_c = countsf[perm]
    prefs_c = prefs[perm]

    def step(carry, cls):
        avail = carry
        demand, countf, pref = cls         # [R], scalar f32, scalar
        has_demand = demand > 0.0          # [R]

        # Capacity bound from node totals: surplus beyond it can never
        # run concurrently on this node set — fence it out before
        # scoring (it never enters the fill below). cap_tot also
        # SUBSUMES the per-node feasibility test: a node whose totals
        # fit one instance has cap_tot >= 1 (an infeasible node's min
        # ratio is < 1, so its floor is already 0), so the fence costs
        # no extra [N, R] pass over the pre-fence kernel.
        ratio_tot = jnp.where(has_demand[None, :],
                              (total + _EPS) /
                              jnp.maximum(demand[None, :], _EPS),
                              jnp.inf)                       # [N, R]
        cap_tot = jnp.floor(jnp.min(ratio_tot, axis=1))      # [N]
        cap_tot = jnp.where(alive, cap_tot, 0.0)
        feas = cap_tot >= 1.0                                # [N]
        any_feasible = jnp.any(feas)
        # int32-safe clamp: a zero-demand class's bound is +inf
        upper_total = jnp.minimum(jnp.sum(cap_tot),
                                  jnp.float32(2 ** 30))
        fenced = jnp.clip(countf - upper_total, 0.0, None)
        fenced = jnp.where(countf > 0, fenced, 0.0)
        target = countf - fenced           # what the fill may attempt

        # Per-node capacity right now.
        ratio = jnp.where(has_demand[None, :],
                          (avail + _EPS) / jnp.maximum(demand[None, :], _EPS),
                          jnp.inf)                           # [N, R]
        cap = jnp.floor(jnp.min(ratio, axis=1))              # [N]
        cap = jnp.where(feas, jnp.minimum(cap, target), 0.0)
        # Live admission bound at this class's commit turn: of the
        # un-fenced target, how much fits the CARRIED availability.
        admitted = jnp.minimum(target, jnp.sum(cap))

        # Critical utilization (hybrid policy's packing signal).
        used = total - avail
        util = jnp.max(jnp.where(total > 0.0, used / jnp.maximum(total, _EPS),
                                 0.0), axis=1)               # [N]

        # --- Phase 1: pack the preferred node while util < threshold ---
        pref_valid = pref >= 0
        p = jnp.maximum(pref, 0)
        # Largest c with util(after c-1 more tasks) < threshold, per resource:
        # used_r + (c-1)*d_r < θ*tot_r  ⇒  c ≤ ceil((θ*tot_r - used_r)/d_r)
        head = threshold * total[p] - used[p]                # [R]
        c_r = jnp.where(has_demand,
                        jnp.ceil(head / jnp.maximum(demand, _EPS)),
                        jnp.inf)                             # [R]
        c_thresh = jnp.clip(jnp.min(c_r), 0.0, None)
        local_take = jnp.where(
            pref_valid & (util[p] < threshold),
            jnp.minimum(jnp.minimum(c_thresh, cap[p]), target),
            0.0)
        local_take = jnp.where(countf > 0, local_take, 0.0)
        avail = avail - jnp.zeros_like(avail).at[p].set(local_take * demand)
        cap = cap.at[p].add(-local_take)
        remaining = target - local_take

        # --- Phase 2: utilization water-fill ---
        # Sequential hybrid places each task on the currently
        # least-utilized node, which converges all receiving nodes to a
        # common utilization level λ. Solve for λ directly by bisection
        # (fixed 40 iters — compiler-friendly): x_n(λ) = #tasks node n
        # absorbs before exceeding level λ.
        used = total - avail                                  # post-phase-1

        def x_of(lam):
            head = lam * total - used                         # [N, R]
            per_r = jnp.where(has_demand[None, :],
                              jnp.floor(head / jnp.maximum(demand[None, :],
                                                           _EPS)),
                              jnp.inf)
            x = jnp.clip(jnp.min(per_r, axis=1), 0.0, cap)    # [N]
            return x

        def bisect(carry, _):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            ge = jnp.sum(x_of(mid)) >= remaining
            return (jnp.where(ge, lo, mid), jnp.where(ge, mid, hi)), None

        (lo, hi), _ = jax.lax.scan(bisect, (jnp.float32(0.0),
                                            jnp.float32(1.0)),
                                   None, length=40)
        x_lo = x_of(lo)
        deficit = jnp.maximum(remaining - jnp.sum(x_lo), 0.0)
        delta = jnp.maximum(x_of(hi) - x_lo, 0.0)
        # Post-fill utilization orders the remainder distribution.
        util_after = jnp.max(
            jnp.where(total > 0.0,
                      (used + x_lo[:, None] * demand[None, :]) /
                      jnp.maximum(total, _EPS), 0.0), axis=1)
        order = jnp.argsort(util_after)                       # [N]
        delta_sorted = delta[order]
        cum = jnp.cumsum(delta_sorted)
        extra_sorted = jnp.clip(deficit - (cum - delta_sorted), 0.0,
                                delta_sorted)
        take_sorted = x_lo[order] + extra_sorted
        taken = jnp.zeros((n_nodes,)).at[order].set(take_sorted)
        avail = avail - taken[:, None] * demand[None, :]

        return avail, (local_take, order.astype(jnp.int32), take_sorted,
                       any_feasible, fenced, admitted, upper_total)

    avail, (local_take, order, take_sorted,
            any_feasible, fenced, admitted, upper) = jax.lax.scan(
        step, avail, (demands_c, counts_c, prefs_c), length=num_classes)

    # ---- residual second fill pass (capacity-feasible backstop) ----
    # The fill's contract is placed == admitted (the live bound at the
    # class's turn); the residual is any admitted-but-unplaced
    # shortfall — 0 in exact arithmetic, so the cond's cheap branch is
    # the steady state and the headline rate pays nothing. Surplus
    # beyond `admitted` is NOT residual: the carried availability is
    # provably exhausted for it this round. placed clamps at admitted:
    # a zero-demand class water-fills count on every node (the host
    # consumes only count assignments), so the raw take sum can
    # legitimately exceed the class count.
    placed1 = jnp.minimum(local_take + jnp.sum(take_sorted, axis=1),
                          admitted)
    residual = jnp.clip(admitted - placed1, 0.0, None)

    def run_residual(op):
        avail, residual = op
        # No preferred-node phase: the residual is pure water-fill.
        no_pref = jnp.full_like(prefs_c, -1)
        avail, (_, order2, take2, _, _, _, _) = jax.lax.scan(
            step, avail, (demands_c, residual, no_pref),
            length=num_classes)
        return avail, order2, take2

    def skip_residual(op):
        avail, _ = op
        zeros_i = jnp.zeros((num_classes, n_nodes), jnp.int32)
        return avail, zeros_i, jnp.zeros((num_classes, n_nodes),
                                         jnp.float32)

    avail, order2, take2 = jax.lax.cond(
        jnp.sum(residual) > 0.0, run_residual, skip_residual,
        (avail, residual))

    # Pack every host-bound output into ONE int32 array so the policy
    # pays for a single device->host transfer per invocation. Rows are
    # gathered back to the CALLER's class order — the scarcity
    # permutation is internal to the commit sequence.
    packed = jnp.concatenate(
        [local_take[:, None], any_feasible.astype(jnp.float32)[:, None],
         fenced[:, None], admitted[:, None], upper[:, None],
         order.astype(jnp.float32), take_sorted,
         order2.astype(jnp.float32), take2], axis=1)   # [K, 4N+5]
    return packed[inv].astype(jnp.int32), avail


# --------------------------------------------------------------------------
# Host-side policy
# --------------------------------------------------------------------------

class DenseSchedule(NamedTuple):
    """One kernel invocation's host-side outputs (caller class order).

    ``fenced[k]`` tasks of class k exceed the node-totals capacity
    bound (the cluster could not hold them even idle); ``admitted[k]``
    is the live bound at the class's commit turn — the fill places
    exactly this many, so ``placed == admitted`` is the kernel's
    completeness contract (docs/scheduler.md)."""

    local_take: np.ndarray    # [K]
    any_feasible: np.ndarray  # [K] bool
    fenced: np.ndarray        # [K]
    admitted: np.ndarray      # [K]
    upper_total: np.ndarray   # [K] totals bound (int32-clamped)
    order: np.ndarray         # [K, N]
    take_sorted: np.ndarray   # [K, N]
    order2: np.ndarray        # [K, N]  residual pass
    take2: np.ndarray         # [K, N]
    new_avail: jax.Array      # [N, R]


class _DenseView:
    """Dense [nodes, resources] mirror of a ClusterResourceManager
    snapshot, rebuilt only when the manager's version changes."""

    def __init__(self):
        self.version = -1
        self.node_ids: List[NodeID] = []
        self.node_index: Dict[NodeID, int] = {}
        self.res_names: List[str] = []
        self.res_index: Dict[str, int] = {}
        self.avail: Optional[np.ndarray] = None   # [Npad, Rpad] f32
        self.total: Optional[np.ndarray] = None
        self.alive: Optional[np.ndarray] = None   # [Npad] bool

    def refresh(self, cluster: ClusterResourceManager,
                extra_resources: Sequence[str]) -> None:
        version = cluster.version()
        extra = [r for r in extra_resources if r not in self.res_index]
        if version == self.version and not extra:
            return
        # Incremental path: between full rebuilds, only rows whose
        # nodes mutated since the cached version are rewritten (the
        # manager's bounded mutation log names them), so steady-state
        # per-batch cost is O(dirty nodes), not O(cluster). Membership
        # changes, log overrun, and new resource names fall back to
        # the full rebuild below.
        if self.version >= 0 and not extra:
            delta = cluster.changes_since(self.version)
            if delta is not None and not delta[1]:
                for nid in delta[0]:
                    i = self.node_index.get(nid)
                    node = cluster.get_node(nid)
                    if i is None or node is None or any(
                            r not in self.res_index for r in node.total):
                        break          # unknown row/column: rebuild
                    self._write_row(i, node)
                else:
                    self.version = version
                    return
        snapshot = cluster.snapshot()
        names = set(extra_resources)
        for node in snapshot.values():
            names.update(node.total)
        self.res_names = sorted(names)
        self.res_index = {r: i for i, r in enumerate(self.res_names)}
        self.node_ids = sorted(snapshot.keys(), key=lambda n: n.hex())
        self.node_index = {n: i for i, n in enumerate(self.node_ids)}
        n_pad = _bucket(max(len(self.node_ids), 1))
        r_pad = _bucket(max(len(self.res_names), 1), minimum=4)
        self.avail = np.zeros((n_pad, r_pad), np.float32)
        self.total = np.zeros((n_pad, r_pad), np.float32)
        self.alive = np.zeros((n_pad,), bool)
        for i, nid in enumerate(self.node_ids):
            self._write_row(i, snapshot[nid])
        self.version = version

    def _write_row(self, i: int, node) -> None:
        self.alive[i] = node.alive
        self.total[i, :] = 0.0
        self.avail[i, :] = 0.0
        # list(): incremental refresh reads the LIVE node dicts, which
        # completion threads mutate concurrently
        for r, v in list(node.total.items()):
            self.total[i, self.res_index[r]] = v
        for r, v in list(node.available.items()):
            j = self.res_index.get(r)
            if j is not None:
                self.avail[i, j] = v

    def demand_vector(self, demand: Dict[str, float]) -> np.ndarray:
        vec = np.zeros((self.total.shape[1],), np.float32)
        for r, v in demand.items():
            vec[self.res_index[r]] = v
        return vec


class TpuSchedulingPolicy(ISchedulingPolicy):
    """Batched scheduling on the accelerator behind the standard seam.

    Semantics match HybridSchedulingPolicy per class: prefer the local
    node until ``scheduler_spread_threshold`` critical utilization, then
    least-utilized feasible nodes; never oversubscribes; a batch is
    committed class-by-class against a carried availability matrix.
    (The top-k randomized tie-break of the CPU policy is replaced by the
    deterministic utilization ordering — batch fill already spreads.)
    """

    name = "tpu"

    def __init__(self, spread_threshold: Optional[float] = None):
        cfg = get_config()
        self._threshold = (spread_threshold if spread_threshold is not None
                           else cfg.scheduler_spread_threshold)
        self._view = _DenseView()

    # -- dense fast path (used by schedule_batch and chip_smoke.py) -------

    def schedule_dense(
        self,
        avail: np.ndarray,       # [N, R]
        total: np.ndarray,       # [N, R]
        alive: np.ndarray,       # [N]
        demands: np.ndarray,     # [K, R]
        counts: np.ndarray,      # [K]
        prefs: np.ndarray,       # [K]
    ) -> "DenseSchedule":
        """Run the kernel on dense matrices; one launch, one d2h."""
        k_pad = _bucket(len(counts), minimum=1)
        if k_pad != len(counts):
            demands = np.pad(demands, ((0, k_pad - len(counts)), (0, 0)))
            prefs = np.pad(prefs, (0, k_pad - len(prefs)),
                           constant_values=-1)
            counts = np.pad(counts, (0, k_pad - len(counts)))
        packed, new_avail = _schedule_classes_kernel(
            jnp.asarray(avail, jnp.float32),
            jnp.asarray(total, jnp.float32),
            jnp.asarray(alive),
            jnp.asarray(demands, jnp.float32),
            jnp.asarray(counts, jnp.int32),
            jnp.asarray(prefs, jnp.int32),
            jnp.float32(self._threshold),
            num_classes=k_pad,
        )
        packed = np.asarray(packed)          # the ONE d2h transfer
        n = avail.shape[0]
        return DenseSchedule(
            local_take=packed[:, 0],
            any_feasible=packed[:, 1].astype(bool),
            fenced=packed[:, 2],
            admitted=packed[:, 3],
            upper_total=packed[:, 4],
            order=packed[:, 5:5 + n],
            take_sorted=packed[:, 5 + n:5 + 2 * n],
            order2=packed[:, 5 + 2 * n:5 + 3 * n],
            take2=packed[:, 5 + 3 * n:5 + 4 * n],
            new_avail=new_avail,
        )

    # -- ISchedulingPolicy ------------------------------------------------

    def schedule_batch(self, cluster: ClusterResourceManager,
                       requests: Sequence[SchedulingRequest]
                       ) -> List[SchedulingResult]:
        if not requests:
            return []
        view = self._view
        view.refresh(cluster, extra_resources=[
            r for req in requests for r in req.demand])
        if not view.node_ids:
            return [SchedulingResult(None, is_infeasible=True)
                    for _ in requests]

        # Group the batch into scheduling classes.
        classes: Dict[tuple, List[int]] = {}
        for i, req in enumerate(requests):
            pref = -1
            if req.preferred_node is not None and not req.avoid_local:
                pref = view.node_index.get(req.preferred_node, -1)
            key = (tuple(sorted(req.demand.items())), pref)
            classes.setdefault(key, []).append(i)

        keys = list(classes.keys())
        demands = np.stack([view.demand_vector(dict(k[0])) for k in keys])
        counts = np.array([len(classes[k]) for k in keys], np.int32)
        prefs = np.array([k[1] for k in keys], np.int32)

        ds = self.schedule_dense(view.avail, view.total, view.alive,
                                 demands, counts, prefs)

        # Expand per-node counts back to per-task results.
        results: List[Optional[SchedulingResult]] = [None] * len(requests)
        for k, key in enumerate(keys):
            indices = classes[key]
            count = len(indices)
            fill = []
            if ds.local_take[k] > 0:
                fill.append(np.full(ds.local_take[k], key[1], np.int32))
            for order_k, take_k in ((ds.order[k], ds.take_sorted[k]),
                                    (ds.order2[k], ds.take2[k])):
                nz = take_k > 0
                if nz.any():
                    fill.append(np.repeat(order_k[nz], take_k[nz]))
            assigned = (np.concatenate(fill) if fill
                        else np.empty(0, np.int32))
            feasible = bool(ds.any_feasible[k])
            fenced_k = int(ds.fenced[k])
            placed = min(len(assigned), count)
            for j, req_i in enumerate(indices):
                if j < placed:
                    results[req_i] = SchedulingResult(
                        view.node_ids[int(assigned[j])])
                elif not feasible:
                    results[req_i] = SchedulingResult(
                        None, is_infeasible=True)
                elif j >= count - fenced_k:
                    # Surplus beyond the class's node-totals capacity
                    # bound: the owner parks it in the unplaceable
                    # ledger (typed) instead of retrying every tick.
                    results[req_i] = SchedulingResult(
                        None, is_fenced=True,
                        fence_bound=int(ds.upper_total[k]))
                else:
                    results[req_i] = SchedulingResult(None)

        # Kernel classes key by (demand, preferred node) but the
        # totals bound is a per-DEMAND cluster-wide quantity: classes
        # sharing a demand would each be granted the full bound and
        # under-fence the joint surplus. Top up across the group.
        by_demand: Dict[tuple, List[int]] = {}
        for k, key in enumerate(keys):
            by_demand.setdefault(key[0], []).append(k)
        for dkey, ks in by_demand.items():
            if len(ks) < 2 or not any(v > 0 for _, v in dkey):
                continue
            upper = int(ds.upper_total[ks[0]])   # same for the group
            group_count = sum(len(classes[keys[k]]) for k in ks)
            need = (max(group_count - upper, 0)
                    - sum(int(ds.fenced[k]) for k in ks))
            for k in ks:
                if need <= 0:
                    break
                for req_i in reversed(classes[keys[k]]):
                    if need <= 0:
                        break
                    r = results[req_i]
                    if (r.node_id is None and not r.is_infeasible
                            and not r.is_fenced):
                        results[req_i] = SchedulingResult(
                            None, is_fenced=True, fence_bound=upper)
                        need -= 1
        return results


_device_rt_s: Optional[float] = None
_device_rt_lock = threading.Lock()
_device_rt_thread: Optional[threading.Thread] = None


def _measure_device_rt() -> None:
    """One-shot measurement of the device dispatch round trip (one
    tiny jitted call plus its d2h transfer): the floor every kernel
    invocation pays, which the adaptive policy weighs against the CPU
    scan's per-task cost."""
    global _device_rt_s
    try:
        f = jax.jit(lambda x: x + 1.0)
        x = jnp.zeros((8,), jnp.float32)
        np.asarray(f(x))                     # compile + first transfer
        t0 = time.perf_counter()
        np.asarray(f(x))
        _device_rt_s = time.perf_counter() - t0
    except Exception:
        # probe thread: nowhere to raise to. inf keeps every live batch
        # on the CPU scan; the warning says why the kernel never runs.
        logger.warning("device round-trip probe failed; the scheduling "
                       "kernel will not be used", exc_info=True)
        _device_rt_s = float("inf")


def _ensure_rt_measurement() -> None:
    global _device_rt_thread
    with _device_rt_lock:
        if _device_rt_s is None and _device_rt_thread is None:
            _device_rt_thread = threading.Thread(
                target=_measure_device_rt, daemon=True,
                name="rtpu-device-rt-probe")
            _device_rt_thread.start()


class AdaptiveSchedulingPolicy(ISchedulingPolicy):
    """Latency/throughput-adaptive production policy for TPU hosts.

    A device invocation has a fixed round-trip floor (one h2d + one d2h
    transfer); a CPU feasibility scan is O(nodes) per task with no
    floor. The kernel therefore pays off only when the batch's CPU-scan
    cost exceeds the measured device round trip: the policy measures
    that round trip once (async, CPU path until known) and routes each
    batch by ``batch × per_task_cpu_cost vs round_trip``; the crossover
    moves with the cluster size (the scan is O(nodes) per task) and is
    counted, not assumed: ``num_kernel_batches`` / ``num_scan_batches``.
    This is the "dispatch small batches at high rate" answer to SURVEY
    §7's dynamic-scheduling-on-static-device hard part.
    """

    name = "tpu_adaptive"

    # Native per-task scan cost model: ~1 µs fixed + ~40 ns per node
    # (measured against native/scheduler.cc at 10k nodes).
    _CPU_FIXED_S = 1e-6
    _CPU_PER_NODE_S = 4e-8

    def __init__(self):
        cfg = get_config()
        self._min_batch = cfg.tpu_scheduler_min_batch
        self._tpu = TpuSchedulingPolicy()
        from ray_tpu._private.scheduler.policy import _cpu_hybrid_policy
        self._cpu = _cpu_hybrid_policy()
        self.num_kernel_batches = 0
        self.num_scan_batches = 0
        _ensure_rt_measurement()

    def _kernel_pays_off(self, n_tasks: int, n_nodes: int) -> bool:
        rt = _device_rt_s
        if rt is None:           # not yet measured: stay on the scan
            return False
        cpu_cost = n_tasks * (self._CPU_FIXED_S
                              + self._CPU_PER_NODE_S * max(n_nodes, 1))
        return cpu_cost > 2.0 * rt

    def schedule_batch(self, cluster: ClusterResourceManager,
                       requests: Sequence[SchedulingRequest]
                       ) -> List[SchedulingResult]:
        if (len(requests) < self._min_batch
                or not self._kernel_pays_off(len(requests),
                                             cluster.num_nodes())):
            self.num_scan_batches += 1
            return self._cpu.schedule_batch(cluster, requests)
        self.num_kernel_batches += 1
        return self._tpu.schedule_batch(cluster, requests)

    def schedule(self, cluster: ClusterResourceManager,
                 request: SchedulingRequest) -> SchedulingResult:
        # Bind the CPU policy's single-task fast path directly — no
        # batch-list wrapping, no adaptive indirection on the p99 path.
        return self._cpu.schedule(cluster, request)


register_policy("tpu", TpuSchedulingPolicy)
register_policy("tpu_adaptive", AdaptiveSchedulingPolicy)
