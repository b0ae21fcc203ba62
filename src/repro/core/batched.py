"""Batched, array-native substrate for placement policies.

PR 1 made each policy a pure function ``decide(ctx) -> TaskDecision`` of a
per-task :class:`~repro.core.policy.PolicyContext`.  That is the right
*semantics*, but a burst of ~1000 simultaneous application instances (the
paper's §V-G protocol) still pays a Python round-trip per task.  This module
introduces the batched counterparts:

  * :class:`FleetSnapshot` — a struct-of-arrays snapshot of the fleet at one
    planning instant: the static device vectors (classes, failure rates,
    bandwidths, memory, join times) plus the dynamic ``(D, N)`` Task_info
    counts that PR 1 scattered across ``Device`` objects and per-call
    ``ClusterState`` accessors.  Registered as a JAX pytree so it can flow
    through ``jit``/``vmap`` boundaries unchanged.
  * :class:`BatchedPolicyContext` — ``(B, D)``-shaped exec/upload/transfer/
    total/pf/feasible tensors for all B tasks of a stage or arrival wave,
    built once per wave by :func:`repro.core.orchestrator.orchestrate_batch`.
    ``row(b)`` recovers the exact scalar :class:`PolicyContext` of row ``b``,
    which is how the default ``Policy.decide_batch`` fallback and the parity
    tests tie the two APIs together.
  * :class:`BatchedDecision` — one device tuple per row, primary first.

The bottom half holds the fused ``jax.numpy`` decision kernels used by the
registered policies' ``decide_batch`` overrides: the IBDASH score-and-
replicate loop (Algorithm 1 lines 30-41) as a ``lax.scan`` over the sorted
candidate queue, vectorised over all B tasks and jitted with a static row
count (B is padded to a bounded shape set — powers of two, then multiples
of 1024 — so a 1000-instance burst compiles a handful of variants, not one
per wave size); LAVEA's masked argmin; and the round-robin gather.  All
kernels run under ``jax.enable_x64`` so their float64 arithmetic is
**bit-identical** to the numpy scalar path — parity is asserted, not
approximate.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from functools import cached_property
from typing import List, Tuple

import numpy as np

from ..obs import hostspans

__all__ = [
    "FLEET_SNAPSHOT_SCHEMA",
    "FleetSnapshot",
    "BatchedPolicyContext",
    "BatchedDecision",
    "BATCH_KERNEL_MIN_ROWS",
    "TOPK_PRUNE_MIN_DEVICES",
    "ibdash_decide_batch",
    "lavea_decide_batch",
    "round_robin_decide_batch",
    "tier_escalation_decide_batch",
]

# Below this many rows the fixed jit-dispatch cost exceeds the fused-kernel
# win, so decide_batch implementations fall back to the (bit-identical)
# per-row scalar rule.
BATCH_KERNEL_MIN_ROWS = 8

# Above this many devices the IBDASH candidate queue is pre-pruned with a
# partial selection (O(D) per row) instead of a full O(D log D) stable
# argsort — only the first n_scan + 1 queue entries are ever reachable, so
# decide_batch cost scales with candidates considered, not raw fleet size.
TOPK_PRUNE_MIN_DEVICES = 256

# THE declarative FleetSnapshot leaf schema — the single source of truth the
# dataclass declaration, the pytree flattener (which iterates ``fields()``,
# so field order IS leaf order), every construction site, and the
# ``snapshot-schema`` lint rule are all checked against.  The schema has
# drifted 12 -> 13 -> 15 -> 17 leaves across PRs 3-10; to add a leaf, extend
# this tuple AND the dataclass together, then let ``python -m repro.analysis``
# point at every construction site that needs the new keyword.
#
# PR 10 factorized the dense ``link_bw`` leaf out of the snapshot: the
# bottleneck rule bw_eff[s, d] = min(up[s], down[d], backhaul[tier[s],
# tier[d]]) is carried as its O(D) + O(T^2) factors (``up_bw``, ``down_bw``,
# ``backhaul`` + the existing ``tiers``), so a snapshot never holds O(D^2)
# state and 100k-device fleets fit.  Sender rows are derived lazily
# (:meth:`FleetSnapshot.link_row`).
FLEET_SNAPSHOT_SCHEMA: Tuple[str, ...] = (
    "t",
    "classes",
    "lams",
    "bandwidths",
    "tiers",
    "up_bw",
    "down_bw",
    "backhaul",
    "mem_total",
    "join_times",
    "alive",
    "surv_grid",
    "survival",
    "counts",
    "queue_len",
    "base",
    "slope",
)


@dataclass(frozen=True)
class FleetSnapshot:
    """Struct-of-arrays view of the whole fleet at one planning instant.

    Everything is indexed by device id (length ``D``); ``counts`` is the
    Task_info matrix at time ``t`` (the paper's "number of running tasks on
    each device at a certain time", §IV-A) and ``queue_len`` its row sum.
    ``base``/``slope`` carry the profiled ED_mc interference table so a
    snapshot is self-contained for Eq. (1) evaluation.  Snapshots are frozen
    and registered as JAX pytrees (arrays are leaves, see
    :func:`_register_pytrees`).
    """

    t: float                 # absolute time of the snapshot
    classes: np.ndarray      # (D,) device-class ids
    lams: np.ndarray         # (D,) failure rates (Table IV)
    bandwidths: np.ndarray   # (D,) DEPRECATED scalar bandwidths (see link_row)
    tiers: np.ndarray        # (D,) fleet tier ids (device/edge_server/cloud)
    # Factorized bottleneck link model (PR 10): bw_eff[s, d] = min(up_bw[s],
    # down_bw[d], backhaul[tiers[s], tiers[d]]), +inf on the diagonal.  The
    # dense (D, D) matrix is never a leaf — derive rows with ``link_row``.
    up_bw: np.ndarray        # (D,) sender uplink rates in bytes/s
    down_bw: np.ndarray      # (D,) receiver downlink rates in bytes/s
    backhaul: np.ndarray     # (T, T) inter-tier backhaul rates (inf = free)
    mem_total: np.ndarray    # (D,) H(ED) in bytes (memory-feasibility data)
    join_times: np.ndarray   # (D,) device join times
    alive: np.ndarray        # (D,) bool: not yet departed at t (churn mask)
    # Availability forecast sampled at t: survival[d, k] = P(device d stays
    # up throughout [t, t + surv_grid[k]]) — exact for scripted maintenance
    # windows, MLE-extrapolated for stochastic churn.  With no forecast
    # installed the leaves are the uniform (K=1) all-ones tensor.
    surv_grid: np.ndarray    # (K,) span offsets of the forecast grid
    survival: np.ndarray     # (D, K) survival probabilities over the grid
    counts: np.ndarray       # (D, N) Task_info at t
    queue_len: np.ndarray    # (D,) total running tasks per device
    base: np.ndarray         # (P, N) ED_mc base latencies c[p, i]
    slope: np.ndarray        # (P, N, N) ED_mc interference slopes m[p, i, j]

    @property
    def n_devices(self) -> int:
        return int(self.classes.shape[0])

    @property
    def n_types(self) -> int:
        return int(self.counts.shape[1])

    def link_row(self, s: int) -> np.ndarray:
        """(D,) sender row ``bw_eff[s, :]`` of the effective link matrix,
        derived from the O(D) factors: ``min(up_bw[s], down_bw[d],
        backhaul[tiers[s], tiers[d]])`` with ``+inf`` at ``d == s`` (a
        co-located transfer crosses no network hop).  Bit-identical to
        slicing the dense matrix the pre-factorization snapshots carried."""
        s = int(s)
        row = np.minimum(self.up_bw[s], self.down_bw)
        row = np.minimum(row, self.backhaul[self.tiers[s], self.tiers])
        row[s] = np.inf
        return row

    @cached_property
    def link_bw(self) -> np.ndarray:
        """(D, D) dense ``bw_eff`` matrix, materialized ON DEMAND from the
        factor leaves (and cached on the instance).  Debug / small-fleet
        convenience only: it is O(D^2) memory, is NOT a pytree leaf, and hot
        paths must slice :meth:`link_row` instead."""
        link = np.minimum(self.up_bw[:, None], self.down_bw[None, :])
        link = np.minimum(
            link, self.backhaul[self.tiers[:, None], self.tiers[None, :]]
        )
        np.fill_diagonal(link, np.inf)
        return link

    def validate(self) -> "FleetSnapshot":
        """Runtime twin of the ``snapshot-schema`` lint rule: assert this
        snapshot's leaf count and order match
        :data:`FLEET_SNAPSHOT_SCHEMA` exactly.

        The pytree flattener iterates ``fields()``, so dataclass field
        order IS pytree leaf order — checking the field tuple checks what
        every jitted kernel will see.  Called once per
        ``ClusterState.snapshot()`` under ``__debug__`` (``python -O``
        strips it from hot production runs).  Returns ``self`` so call
        sites can chain."""
        names = tuple(f.name for f in fields(self))
        if names != FLEET_SNAPSHOT_SCHEMA:
            raise TypeError(
                f"FleetSnapshot leaf drift: instance flattens to "
                f"{list(names)} but FLEET_SNAPSHOT_SCHEMA declares "
                f"{list(FLEET_SNAPSHOT_SCHEMA)}; update the schema, the "
                "dataclass, and every construction site together"
            )
        return self


@dataclass(frozen=True)
class BatchedPolicyContext:
    """Everything a policy may inspect to place B tasks at once.

    Row ``b`` is one task.  Rows of one batch were built against the same
    cluster state — a stage of one application, or a whole arrival wave —
    so a batched decision is defined to equal deciding the rows one by one
    in order (stateful policies consume their rng/cursor once per row; see
    ``Policy.decide_batch``).

    Storage is a deduplicated struct-of-arrays: a burst of ~1000 instances
    of a few application types produces waves whose rows are largely
    IDENTICAL (same task type, model, parents, bucketed start time), so the
    ``*_pool`` tensors hold only the G << B distinct context rows and
    ``row_pool`` maps each row to its pool entry.  The pool key covers
    everything a context row is a function of, so ``pool_row == row`` holds
    exactly — stateless policies may decide once per pool entry and fan the
    decision out (bit-identical memoisation of a pure function), while the
    classic ``(B, D)`` views (``exec_lat``, ``total``, ``pf``, ...)
    materialise lazily for stateful policies and the scalar ``row(b)``
    bridge.  ``fleet`` carries the shared static device vectors.
    """

    tasks: Tuple[str, ...]       # (B,) task names (error reporting)
    ttypes: np.ndarray           # (B,) task-type indices
    t_start: np.ndarray          # (B,) absolute estimated starts
    stage_offset: np.ndarray     # (B,) offsets from each app's arrival
    row_pool: np.ndarray         # (B,) row -> distinct-context pool entry
    pool_first: np.ndarray       # (G,) pool entry -> its first row
    exec_pool: np.ndarray        # (G, D) Eq. (1) execution latency
    upload_pool: np.ndarray      # (G, D) L(M(T_i)) model-upload latency
    transfer_pool: np.ndarray    # (G, D) L(T_i)_d input-transfer latency
    total_pool: np.ndarray       # (G, D) Eq. (2): exec + upload + transfer
    feasible_pool: np.ndarray    # (G, D) bool memory-feasibility mask
    pf_pool: np.ndarray          # (G, D) F(T_i) per device
    # Per-candidate forecast survival over each row's estimated execution
    # span: S_d(t_start, t_start + total[g, d]), evaluated EXACTLY from the
    # installed forecast (all-ones when none is installed, so policies fall
    # back bit-identically to the memoryless pf column).
    survival_pool: np.ndarray    # (G, D)
    # Task_info snapshots are pooled separately by T_alloc bucket.
    counts_pool: np.ndarray      # (Gc, D, N) distinct Task_info snapshots
    queue_pool: np.ndarray       # (Gc, D) their queue lengths
    bucket_inv: np.ndarray       # (B,) row -> counts/queue pool index
    # Shared fleet vectors.  NOTE: the snapshot is taken at the wave-stage's
    # FIRST row's start time — its static vectors (classes, lams, ...) hold
    # for every row, but in a multi-time wave its dynamic `counts`/
    # `queue_len` describe only that reference instant; per-row dynamic
    # state lives in `counts_pool`/`queue_pool`/`bucket_inv` (or the lazy
    # `counts`/`queue_len` views).
    fleet: FleetSnapshot

    # -- lazily materialised (B, D[, N]) views -------------------------------
    def _expand(self, pool: np.ndarray, inv: np.ndarray) -> np.ndarray:
        """Per-row view of a pool: broadcast when the pool is one entry,
        gather by ``inv`` otherwise."""
        if pool.shape[0] == 1:
            return np.broadcast_to(
                pool[0], (len(self.tasks),) + pool.shape[1:]
            )
        return pool[inv]

    @cached_property
    def exec_lat(self) -> np.ndarray:
        return self._expand(self.exec_pool, self.row_pool)

    @cached_property
    def upload(self) -> np.ndarray:
        return self._expand(self.upload_pool, self.row_pool)

    @cached_property
    def transfer(self) -> np.ndarray:
        return self._expand(self.transfer_pool, self.row_pool)

    @cached_property
    def total(self) -> np.ndarray:
        return self._expand(self.total_pool, self.row_pool)

    @cached_property
    def feasible(self) -> np.ndarray:
        return self._expand(self.feasible_pool, self.row_pool)

    @cached_property
    def pf(self) -> np.ndarray:
        return self._expand(self.pf_pool, self.row_pool)

    @cached_property
    def survival(self) -> np.ndarray:
        """(B, D) per-candidate forecast survival over each row's span."""
        return self._expand(self.survival_pool, self.row_pool)

    @cached_property
    def counts(self) -> np.ndarray:
        """(B, D, N) Task_info at each row's t_start (lazy; see pools)."""
        return self._expand(self.counts_pool, self.bucket_inv)

    @cached_property
    def queue_len(self) -> np.ndarray:
        """(B, D) LAVEA's SQLF signal per row (lazy; see pools)."""
        return self._expand(self.queue_pool, self.bucket_inv)

    @property
    def n_rows(self) -> int:
        return len(self.tasks)

    @property
    def n_devices(self) -> int:
        return int(self.exec_pool.shape[1])

    @property
    def n_distinct(self) -> int:
        """Number of distinct context rows (pool entries)."""
        return int(self.exec_pool.shape[0])

    # shared static fleet vectors, delegated for policy convenience ----------
    @property
    def classes(self) -> np.ndarray:
        return self.fleet.classes

    @property
    def lams(self) -> np.ndarray:
        return self.fleet.lams

    @property
    def join_times(self) -> np.ndarray:
        return self.fleet.join_times

    @property
    def bandwidths(self) -> np.ndarray:
        return self.fleet.bandwidths

    @property
    def tiers(self) -> np.ndarray:
        return self.fleet.tiers

    def link_row(self, s: int) -> np.ndarray:
        """(D,) sender row of the effective link matrix (factorized)."""
        return self.fleet.link_row(s)

    @property
    def link_bw(self) -> np.ndarray:
        """(D, D) dense bw_eff matrix, materialized on demand from the
        snapshot's factor leaves — debug/small-fleet only (O(D^2))."""
        return self.fleet.link_bw

    @property
    def mem_total(self) -> np.ndarray:
        return self.fleet.mem_total

    @property
    def alive(self) -> np.ndarray:
        """(D,) bool: devices not yet departed when the wave was planned.
        Already ANDed into ``feasible``; exposed for custom policies that
        build their own masks."""
        return self.fleet.alive

    def feasible_ids(self, b: int) -> np.ndarray:
        return np.flatnonzero(self.feasible_pool[self.row_pool[b]])

    def estimates_at(
        self, b: int, did: int
    ) -> Tuple[float, float, float, float]:
        """(exec, upload, transfer, pf) of device ``did`` for row ``b``."""
        g = self.row_pool[b]
        return (
            float(self.exec_pool[g, did]),
            float(self.upload_pool[g, did]),
            float(self.transfer_pool[g, did]),
            float(self.pf_pool[g, did]),
        )

    def primary_estimates(
        self, dids: np.ndarray
    ) -> Tuple[list, list, list, list]:
        """Bulk (exec, upload, transfer, pf) lists at one device per row
        (the chosen primaries) — four fused gathers instead of 4B scalar
        reads."""
        g = self.row_pool
        return (
            self.exec_pool[g, dids].tolist(),
            self.upload_pool[g, dids].tolist(),
            self.transfer_pool[g, dids].tolist(),
            self.pf_pool[g, dids].tolist(),
        )

    def row(self, b: int):
        """The exact scalar :class:`PolicyContext` of row ``b`` — the bridge
        between the batched and scalar APIs (used by the default
        ``decide_batch`` fallback and the parity tests)."""
        from .policy import PolicyContext  # deferred: policy imports us

        g = self.row_pool[b]
        gc = self.bucket_inv[b]
        feasible = self.feasible_pool[g]
        return PolicyContext(
            task=self.tasks[b],
            ttype=int(self.ttypes[b]),
            t_start=float(self.t_start[b]),
            stage_offset=float(self.stage_offset[b]),
            exec_lat=self.exec_pool[g],
            upload=self.upload_pool[g],
            transfer=self.transfer_pool[g],
            total=self.total_pool[g],
            feasible=feasible,
            feasible_ids=np.flatnonzero(feasible),
            pf=self.pf_pool[g],
            lams=self.fleet.lams,
            join_times=self.fleet.join_times,
            queue_len=self.queue_pool[gc],
            counts=self.counts_pool[gc],
            classes=self.fleet.classes,
            tiers=self.fleet.tiers,
            alive=self.fleet.alive,
            survival=self.survival_pool[g],
        )


@dataclass(frozen=True)
class BatchedDecision:
    """A policy's verdict for a whole batch: row-aligned device tuples,
    primary first; an empty tuple marks the row's task unplaceable."""

    devices: Tuple[Tuple[int, ...], ...]

    @property
    def n_rows(self) -> int:
        return len(self.devices)

    def __iter__(self):
        return iter(self.devices)

    def __getitem__(self, b: int) -> Tuple[int, ...]:
        return self.devices[b]


# -- JAX plumbing -------------------------------------------------------------
_JAX_STATE: dict = {}


def _register_pytrees(jax) -> None:
    """Register the frozen context dataclasses as pytrees (arrays = leaves,
    task names = aux data) so snapshots/contexts pass through jax transforms."""
    from jax.tree_util import register_pytree_node

    def flatten_fleet(s: FleetSnapshot):
        names = [f.name for f in fields(FleetSnapshot)]
        return tuple(getattr(s, n) for n in names), tuple(names)

    def unflatten_fleet(names, vals):
        return FleetSnapshot(**dict(zip(names, vals)))

    def flatten_batch(c: BatchedPolicyContext):
        names = [f.name for f in fields(BatchedPolicyContext) if f.name != "tasks"]
        return tuple(getattr(c, n) for n in names), (tuple(names), c.tasks)

    def unflatten_batch(aux, vals):
        names, tasks = aux
        return BatchedPolicyContext(tasks=tasks, **dict(zip(names, vals)))

    register_pytree_node(FleetSnapshot, flatten_fleet, unflatten_fleet)
    register_pytree_node(BatchedPolicyContext, flatten_batch, unflatten_batch)


def _jax():
    """Import jax lazily (keeps ``repro.core`` import-light), register the
    pytrees once, and build the jitted kernels."""
    if "jnp" in _JAX_STATE:
        return _JAX_STATE
    import jax
    import jax.numpy as jnp

    _register_pytrees(jax)

    def ibdash_scan_kernel(s_total, s_pf, n_feas, alpha, beta, gamma):
        """Algorithm 1's score-and-replicate loop (lines 29-41) for all B
        rows at once: a ``lax.scan`` over the pre-sorted candidate queue
        carrying one ``active`` lane per row — a lane goes (and stays)
        inactive exactly when the scalar ``while`` would have exited or hit
        its ``break``.

        Inputs are the first ``K = n_scan + 1`` columns of each task's
        priority queue (lines 16-18), already sorted ascending by total
        latency.  Every scalar iteration either accepts a replica (at most
        ``gamma`` times) or breaks, so ``n_scan = min(gamma + 1, D - 1)``
        steps cover every reachable state.  The sort itself stays in numpy:
        XLA's CPU sort/top_k measured ~5x slower than ``np.argsort`` at the
        (4096, 100) wave shapes this serves (flip to a jnp sort when
        running the kernel on an accelerator).
        """
        best = s_total[:, 0]
        l_ref = jnp.maximum(best, 1e-9)
        comb0 = s_pf[:, 0]
        w0 = alpha * (best / l_ref) + (1 - alpha) * comb0      # line 29
        n_rows = s_total.shape[0]
        n_scan = s_total.shape[1] - 1

        def step(carry, xs):
            active, comb, w_s, t_rep = carry
            qi, c_total, c_pf = xs
            cond = (active & (comb >= beta) & (t_rep < gamma)
                    & (qi < n_feas))                           # line 30
            new_fail = comb * c_pf
            w_new = alpha * (c_total / l_ref) + (1 - alpha) * new_fail
            accept = cond & (w_new <= w_s)                     # line 34
            comb = jnp.where(accept, new_fail, comb)
            w_s = jnp.where(accept, w_new, w_s)
            t_rep = t_rep + accept                             # line 37
            # rejection => break (line 39); cond failure => loop exit
            return (accept, comb, w_s, t_rep), accept

        qis = jnp.arange(1, n_scan + 1)
        _, accepts = jax.lax.scan(
            step,
            (jnp.ones(n_rows, bool), comb0, w0, jnp.zeros(n_rows, jnp.int32)),
            (qis, s_total[:, 1:].T, s_pf[:, 1:].T),
        )
        return accepts.T                                       # (B, n_scan)

    def lavea_kernel(queue_len, feasible):
        """Shortest Queue Length First: masked argmin per row."""
        return jnp.argmin(jnp.where(feasible, queue_len, jnp.inf), axis=1)

    def round_robin_kernel(feasible, targets):
        """Select each row's ``targets[b]``-th feasible device."""
        pos = jnp.cumsum(feasible, axis=1) - 1
        match = feasible & (pos == targets[:, None])
        return jnp.argmax(match, axis=1)

    def tier_escalation_kernel(total, feasible, tiers, budget, n_tiers):
        """Tier escalation for all B rows: per level L (device -> edge ->
        cloud) take the masked argmin over feasible devices at tiers <= L,
        accept the first level whose best candidate meets the latency
        budget, fall back to the global feasible argmin.  ``n_tiers`` is
        static so the tiny level loop unrolls."""
        B = total.shape[0]
        rows = jnp.arange(B)
        picked = jnp.zeros(B, jnp.int64)
        chosen = jnp.zeros(B, bool)
        for lv in range(n_tiers):
            masked = jnp.where(feasible & (tiers[None, :] <= lv), total, jnp.inf)
            best = jnp.argmin(masked, axis=1)
            best_val = masked[rows, best]
            take = ~chosen & jnp.isfinite(best_val) & (best_val <= budget)
            picked = jnp.where(take, best, picked)
            chosen = chosen | take
        gbest = jnp.argmin(jnp.where(feasible, total, jnp.inf), axis=1)
        return jnp.where(chosen, picked, gbest)

    _JAX_STATE.update(
        jnp=jnp,
        enable_x64=jax.enable_x64,
        ibdash_scan_kernel=jax.jit(ibdash_scan_kernel),
        lavea_kernel=jax.jit(lavea_kernel),
        round_robin_kernel=jax.jit(round_robin_kernel),
        tier_escalation_kernel=jax.jit(
            tier_escalation_kernel, static_argnums=(4,)
        ),
    )
    return _JAX_STATE


def _pad_rows(arr: np.ndarray, n_pad: int, fill) -> np.ndarray:
    if n_pad == 0:
        return arr
    pad = np.full((n_pad,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _padded(B: int) -> int:
    """Pad the row count to a bounded set of shapes so a burst's shrinking
    wave sizes reuse compiled kernels: powers of two up to 1024, then
    multiples of 1024 (tighter than pow2 for the big waves)."""
    if B <= 1024:
        return 1 << max(B - 1, 0).bit_length()
    return -(-B // 1024) * 1024


# -- fused decision kernels (numpy in, tuples out) ----------------------------
def _topk_stable(masked: np.ndarray, k: int) -> np.ndarray:
    """First ``k`` columns of the row-wise stable ascending argsort of
    ``masked``, without sorting all D columns.

    ``np.partition`` finds each row's k-th smallest value (the selection
    boundary) in O(D); everything strictly below the boundary survives, and
    boundary ties are resolved to the LOWEST device ids — exactly the
    entries a stable full sort would keep — so the result is bit-identical
    to ``np.argsort(masked, kind="stable")[:, :k]`` including tie-breaks.
    Only the <= k survivors are then sorted: O(D + k log k) per row."""
    B = masked.shape[0]
    boundary = np.partition(masked, k - 1, axis=1)[:, k - 1]
    out = np.empty((B, k), np.int64)
    for b in range(B):
        below = np.flatnonzero(masked[b] < boundary[b])
        ties = np.flatnonzero(masked[b] == boundary[b])[: k - below.size]
        cand = np.concatenate([below, ties])
        out[b] = cand[np.argsort(masked[b, cand], kind="stable")]
    return out


def ibdash_decide_batch(
    total: np.ndarray,
    pf: np.ndarray,
    feasible: np.ndarray,
    alpha: float,
    beta: float,
    gamma: int,
) -> List[Tuple[int, ...]]:
    """One fused call of the IBDASH score-and-replicate rule for B tasks.

    Bit-identical to looping the scalar rule: float64 arithmetic under
    ``jax.enable_x64``, stable sorts, and the same IEEE expressions per step.
    """
    B, D = total.shape
    n_feas = feasible.sum(axis=1)
    n_scan = min(int(gamma) + 1, D - 1)  # a scalar iteration accepts or breaks
    # lines 16-18: the priority queue == stable ascending sort over L(T_i)
    # with infeasible devices pushed to +inf.  Only the first n_scan + 1
    # entries are reachable, so the rest of the permutation is discarded —
    # and on big fleets never even computed (partial selection, same order).
    with hostspans.span("policy.select", G=B, D=D, n_scan=n_scan):
        masked = np.where(feasible, total, np.inf)
        if D > TOPK_PRUNE_MIN_DEVICES and n_scan + 1 < D:
            order = _topk_stable(masked, n_scan + 1)
        else:
            order = np.argsort(masked, axis=1, kind="stable")[:, : n_scan + 1]
        s_total = np.take_along_axis(total, order, axis=1)
        s_pf = np.take_along_axis(pf, order, axis=1)
    if n_scan > 0:
        st = _jax()
        n_pad = _padded(B) - B
        with (hostspans.span("policy.kernel", G=B, rows=B + n_pad),
              st["enable_x64"](True)):
            accepts = st["ibdash_scan_kernel"](
                _pad_rows(np.asarray(s_total, np.float64), n_pad, 1.0),
                _pad_rows(np.asarray(s_pf, np.float64), n_pad, 0.0),
                _pad_rows(np.asarray(n_feas, np.int64), n_pad, D),
                float(alpha), float(beta), int(gamma),
            )
            accepts = np.asarray(accepts)[:B]
    else:
        accepts = np.zeros((B, 0), bool)
    n_extra = accepts.sum(axis=1)
    primary = order[:, 0]
    out: List[Tuple[int, ...]] = []
    for b in range(B):
        if n_feas[b] == 0:
            out.append(())
        elif n_extra[b] == 0:                       # the common, no-replica row
            out.append((int(primary[b]),))
        else:
            extras = order[b, np.flatnonzero(accepts[b]) + 1]
            out.append((int(primary[b]), *(int(d) for d in extras)))
    return out


def lavea_decide_batch(
    queue_len: np.ndarray, feasible: np.ndarray
) -> List[Tuple[int, ...]]:
    """Fused SQLF for B tasks: masked argmin (first minimum, like the
    scalar ``ids[argmin(queue[ids])]``)."""
    n_feas = feasible.sum(axis=1)
    if queue_len.shape[0] >= BATCH_KERNEL_MIN_ROWS:
        st = _jax()
        B = queue_len.shape[0]
        n_pad = _padded(B) - B
        with (hostspans.span("policy.kernel", G=B, rows=B + n_pad),
              st["enable_x64"](True)):
            picked = st["lavea_kernel"](
                _pad_rows(np.asarray(queue_len, np.float64), n_pad, 0.0),
                _pad_rows(np.asarray(feasible, bool), n_pad, True),
            )
            picked = np.asarray(picked)[:B]
    else:
        masked = np.where(feasible, queue_len, np.inf)
        picked = np.argmin(masked, axis=1)
    return [
        (int(picked[b]),) if n_feas[b] > 0 else ()
        for b in range(queue_len.shape[0])
    ]


def tier_escalation_decide_batch(
    total: np.ndarray,
    feasible: np.ndarray,
    tiers: np.ndarray,
    budget: float,
) -> List[Tuple[int, ...]]:
    """Fused tier-escalation rule for B tasks.

    For each row, widen the candidate set one tier level at a time (devices
    first, then edge servers, then cloud) and place on the min-``total``
    candidate of the first level whose best option meets ``budget``; if even
    the whole fleet misses the budget, place on the global feasible best.
    Bit-identical to looping the scalar rule (same float64 masked argmins,
    first-minimum tie-break)."""
    B, D = total.shape
    n_feas = feasible.sum(axis=1)
    n_tiers = int(tiers.max()) + 1 if tiers.size else 1
    if B >= BATCH_KERNEL_MIN_ROWS:
        st = _jax()
        n_pad = _padded(B) - B
        with (hostspans.span("policy.kernel", G=B, rows=B + n_pad),
              st["enable_x64"](True)):
            picked = st["tier_escalation_kernel"](
                _pad_rows(np.asarray(total, np.float64), n_pad, 1.0),
                _pad_rows(np.asarray(feasible, bool), n_pad, False),
                np.asarray(tiers, np.int64),
                float(budget),
                n_tiers,
            )
            picked = np.asarray(picked)[:B]
    else:
        rows = np.arange(B)
        picked = np.zeros(B, np.int64)
        chosen = np.zeros(B, bool)
        for lv in range(n_tiers):
            masked = np.where(feasible & (tiers[None, :] <= lv), total, np.inf)
            best = np.argmin(masked, axis=1)
            best_val = masked[rows, best]
            take = ~chosen & np.isfinite(best_val) & (best_val <= budget)
            picked = np.where(take, best, picked)
            chosen |= take
        gbest = np.argmin(np.where(feasible, total, np.inf), axis=1)
        picked = np.where(chosen, picked, gbest)
    return [(int(picked[b]),) if n_feas[b] > 0 else () for b in range(B)]


def round_robin_decide_batch(
    feasible: np.ndarray, cursor: int
) -> Tuple[List[Tuple[int, ...]], int]:
    """Fused cyclic assignment.  Batch semantics: rows are served in order
    and the cursor advances once per row with a non-empty feasible set —
    exactly what looping the scalar rule does.  Returns (decisions, new
    cursor)."""
    B = feasible.shape[0]
    sizes = feasible.sum(axis=1)
    nonempty = sizes > 0
    before = np.cumsum(nonempty) - nonempty          # non-empty rows before b
    targets = np.where(nonempty, (cursor + before) % np.maximum(sizes, 1), 0)
    if B >= BATCH_KERNEL_MIN_ROWS:
        st = _jax()
        n_pad = _padded(B) - B
        with (hostspans.span("policy.kernel", G=B, rows=B + n_pad),
              st["enable_x64"](True)):
            picked = st["round_robin_kernel"](
                _pad_rows(np.asarray(feasible, bool), n_pad, True),
                _pad_rows(np.asarray(targets, np.int64), n_pad, 0),
            )
            picked = np.asarray(picked)[:B]
    else:
        pos = np.cumsum(feasible, axis=1) - 1
        match = feasible & (pos == targets[:, None])
        picked = np.argmax(match, axis=1)
    decisions = [
        (int(picked[b]),) if nonempty[b] else () for b in range(B)
    ]
    return decisions, cursor + int(nonempty.sum())
