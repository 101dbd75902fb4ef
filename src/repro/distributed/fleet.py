"""Fleet-sharding layer: partition the instance axis over a device mesh.

``smartfill_batched`` and ``simulate_ensemble`` are one-device programs:
a ``vmap`` over the instance axis.  At cloud scale the ensemble itself
outgrows one accelerator — thousands of tenants planned per decision
round, heSRPT-style policy sweeps over tens of thousands of workload
instances (Berg et al.) — so this module shards that axis over a 1-D
``jax.sharding.Mesh`` with ``shard_map``:

``plan_sharded``
    ``smartfill_batched`` with instances partitioned across the mesh.
``simulate_ensemble_sharded``
    ``simulate_ensemble`` with workloads partitioned across the mesh
    (policies stay unrolled, as in the single-device runner).

Both wrap the same driver (``_run_sharded``):

  * the instance count N is padded up to a multiple of the device count
    (and of the chunk size) — padded instances are **inert**: sizes,
    weights and live-job counts pad with zeros (m = 0 rows are masked
    no-ops inside the solver; size-0 jobs never run in the engine),
    while speedup/policy parameter leaves pad by edge replication so the
    padded rows still hold *valid* family parameters;
  * instances are laid out as a ``(n_chunks, chunk)`` megabatch and the
    per-device program is a ``lax.scan`` over chunks around the vmapped
    single-instance core — so a sweep with K ≫ device memory streams
    through the mesh in bounded-size chunks (``chunk_size`` bounds the
    live working set; the scan reuses it every step);
  * there is **no cross-device communication**: every instance is an
    independent solve, so the shard_map body is collective-free and the
    sharded result equals the single-device result instance by
    instance.

Per-instance batching follows the ensemble convention: any pytree leaf
of ``sp`` (or of a policy) with leading dimension N is split across the
mesh alongside its instances; all other leaves are replicated.

The mesh resolution order is: explicit ``mesh=`` argument, then the
innermost active ``with Mesh(...)`` context (``sharding.active_mesh``),
then a fresh 1-D mesh over all local devices (``fleet_mesh()``).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.batch import (BatchedSmartFillSchedule, _prepare,
                              check_axes_unambiguous, hetero_order_batch,
                              validate_padded_instances)
from repro.core.simulator import (EnsembleResult, _check_policy_budget,
                                  _fault_B0, _fault_n_events, _prepared_faults,
                                  _sim_core, _validate_budget,
                                  _validate_workload, _warn_event_budget,
                                  n_events_for)
from repro.core.smartfill import _fast_ok, _solve
from repro.core.speedup import collapse_homogeneous

from .sharding import active_mesh

__all__ = [
    "FleetStreamResult",
    "active_fleet_mesh",
    "fleet_mesh",
    "plan_classes_sharded",
    "plan_sharded",
    "serve_streams_sharded",
    "simulate_ensemble_sharded",
]

FLEET_AXIS = "fleet"


def active_fleet_mesh() -> Mesh | None:
    """The innermost active ``with Mesh(...)`` when it is 1-D, else None.

    The dispatch predicate consumers use (sched/cluster.py planning,
    serve/admission.py's simulate estimator): a 1-D mesh context means
    "shard the instance axis here"; a multi-axis (model-parallel) mesh
    is somebody else's and is left alone.  Only *concrete* meshes
    qualify — ``active_mesh()`` surfaces a ``set_mesh`` mesh as an
    ``AbstractMesh`` (axis names/sizes but no device placement), which
    shard_map cannot be driven with; those fall through to the
    single-device path instead of crashing.
    """
    mesh = active_mesh()
    if (mesh is not None and len(mesh.axis_names) == 1
            and getattr(mesh, "devices", None) is not None):
        return mesh
    return None


def fleet_mesh(n_devices: int | None = None,
               axis_name: str = FLEET_AXIS) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices (all by
    default) — the instance-axis mesh both sharded entry points expect.

    On CPU, force a multi-device host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before
    jax initializes; see examples/fleet_sweep.py).
    """
    devs = np.asarray(jax.devices())
    if n_devices is not None:
        if n_devices > devs.size:
            raise ValueError(
                f"asked for {n_devices} devices, only {devs.size} present")
        devs = devs[:n_devices]
    return Mesh(devs, (axis_name,))


def _resolve_mesh(mesh: Mesh | None) -> Mesh:
    """Explicit mesh, else the active 1-D mesh context, else all devices."""
    if mesh is None:
        mesh = active_fleet_mesh()      # multi-axis/abstract: not ours
    if mesh is None:
        mesh = fleet_mesh()
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"fleet sharding needs a 1-D mesh, got axes {mesh.axis_names}")
    if getattr(mesh, "devices", None) is None:
        raise ValueError(
            "fleet sharding needs a concrete Mesh with device placement, "
            "got an abstract mesh — build one with fleet_mesh()")
    return mesh


class _SplitLeaves:
    """Partition a pytree's leaves into per-instance and shared lists.

    A leaf is per-instance iff its leading dimension equals N (the
    ensemble-runner convention).  ``key`` — (treedef, is_batched) — is
    hashable and fully determines ``_merge_leaves``, so the compiled
    driver programs cache on it (repeated calls with the same pytree
    *structure* must not re-jit; an admission controller plans every
    decision round).
    """

    def __init__(self, tree, N: int):
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.is_batched = tuple(
            hasattr(l, "ndim") and getattr(l, "ndim", 0) >= 1
            and l.shape[0] == N for l in leaves)
        self.batched = tuple(l for l, b in zip(leaves, self.is_batched) if b)
        self.shared = tuple(l for l, b in zip(leaves, self.is_batched)
                            if not b)

    @property
    def key(self):
        return (self.treedef, self.is_batched)


def _merge_leaves(key, batched, shared):
    """Rebuild the original pytree from split leaf lists (see above)."""
    treedef, is_batched = key
    batched, shared = list(batched), list(shared)
    leaves = [batched.pop(0) if b else shared.pop(0) for b in is_batched]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _pad_rows(leaf, total: int, edge: bool):
    """Pad a leading-dim-N leaf up to ``total`` rows.

    ``edge=True`` replicates the last row (speedup/policy parameters:
    padded instances keep *valid* family params so the solver cannot
    NaN on them); ``edge=False`` pads zeros (sizes/weights/counts: the
    inert-instance convention)."""
    leaf = jnp.asarray(leaf)
    n = leaf.shape[0]
    if n == total:
        return leaf
    if edge:
        tail = jnp.broadcast_to(leaf[-1:],
                                (total - n,) + leaf.shape[1:])
    else:
        tail = jnp.zeros((total - n,) + leaf.shape[1:], leaf.dtype)
    return jnp.concatenate([leaf, tail], axis=0)


def _chunk_layout(N: int, D: int, chunk_size: int | None):
    """(total, n_chunks, chunk): instance-axis padding plan.

    ``chunk`` is the global instances per scan step — a multiple of the
    device count D, defaulting to everything in one step.  ``total`` =
    n_chunks · chunk ≥ N is what the instance axis pads to."""
    if N < 1:
        raise ValueError("need at least one instance")
    if chunk_size is None:
        chunk = math.ceil(N / D) * D
    else:
        if chunk_size < 1:
            raise ValueError("chunk_size must be ≥ 1")
        chunk = math.ceil(chunk_size / D) * D
    n_chunks = math.ceil(N / chunk)
    return n_chunks * chunk, n_chunks, chunk


@functools.lru_cache(maxsize=256)
def _sharded_program(fn, mesh: Mesh):
    """The compiled mesh program for one (instance-map, mesh) pair.

    ``fn`` must be a cached module-level object (``_plan_fn`` /
    ``_sim_fn`` below return the same function for the same static
    key), so repeated planning calls reuse the jitted program instead
    of re-tracing — jit itself handles new *shapes* (chunk layouts) on
    the same callable.

    Layout: each batched leaf arrives as (n_chunks, chunk, …); axis 1
    shards over the mesh (prefix spec, so the pytree structure never
    enters the cache key) and the per-device body scans axis 0 — one
    bounded (chunk/D)-instance solve per step, no collectives.
    """
    axis = mesh.axis_names[0]

    def body(bat_local, sh):
        def step(carry, sl):
            return carry, fn(sl, sh)

        _, ys = lax.scan(step, 0, bat_local)
        return ys

    # check_vma=False: the body is collective-free by construction (every
    # instance is an independent solve), so there is nothing for the
    # varying-axes checker to prove.
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(None, axis), P()),
                                 out_specs=P(None, axis),
                                 check_vma=False))


def _run_sharded(mesh: Mesh, fn, batched, shared, N: int,
                 chunk_size: int | None):
    """Drive ``fn`` over the instance axis: shard → scan chunks → vmap.

    ``batched``: pytree whose leaves are (total, …) instance-major
    arrays (already padded via ``_pad_rows``); ``shared``: replicated
    pytree.  ``fn(slice, shared)`` maps a (rows, …) slice to a pytree
    of (rows, …) outputs.  Returns outputs trimmed back to N rows.
    """
    D = mesh.devices.size
    total, n_chunks, chunk = _chunk_layout(N, D, chunk_size)
    resh = jax.tree_util.tree_map(
        lambda l: l.reshape((n_chunks, chunk) + l.shape[1:]), batched)
    out = _sharded_program(fn, mesh)(resh, shared)
    return jax.tree_util.tree_map(
        lambda l: l.reshape((total,) + l.shape[2:])[:N], out)


@functools.lru_cache(maxsize=256)
def _plan_fn(sp_key, coarse: int, descent_iters: int, cap_iters: int,
             fast: bool, stol_rel: float | None = None):
    """Cached instance-map for planning: one stable callable per static
    configuration, so ``_sharded_program`` can key its jit cache on it."""

    def fn(sl, shared):
        x, w, b, mm, sp_b = sl

        def one(x1, w1, b1, m1, sp_b1):
            spv = _merge_leaves(sp_key, sp_b1, shared)
            return _solve(spv, x1, w1, b1, m1,
                          coarse, descent_iters, cap_iters, fast,
                          stol_rel=stol_rel)

        return jax.vmap(one)(x, w, b, mm, sp_b)

    return fn


@functools.lru_cache(maxsize=256)
def _sim_fn(sp_key, pol_key, n_events: int, faulted: bool = False):
    """Cached instance-map for ensemble simulation (cf. ``_plan_fn``).

    With ``faulted`` the slice carries the prepared per-instance fault
    arrays (times/kinds/jobs/values, each (rows, S+1)) and the core runs
    its fault-aware step with each lane's budget carry seeded from the
    (possibly per-instance) policy ``B`` leaf."""

    def fn(sl, shared):
        sp_sh, pol_sh, rtol = shared

        if faulted:
            x, w, arr, sp_b, pol_b, flt = sl

            def one(x1, w1, a1, sp_b1, pol_b1, f1):
                spv = _merge_leaves(sp_key, sp_b1, sp_sh)
                pv = _merge_leaves(pol_key, pol_b1, pol_sh)
                T, finished, _, _, valid = _sim_core(
                    spv, pv, x1, w1, a1, rtol, n_events,
                    faults=f1, B0=pv.B)
                J = jnp.where(finished, jnp.sum(w1 * T), jnp.inf)
                return T, J, finished, jnp.sum(valid)

            return jax.vmap(one)(x, w, arr, sp_b, pol_b, flt)

        x, w, arr, sp_b, pol_b = sl

        def one(x1, w1, a1, sp_b1, pol_b1):
            spv = _merge_leaves(sp_key, sp_b1, sp_sh)
            pv = _merge_leaves(pol_key, pol_b1, pol_sh)
            T, finished, _, _, valid = _sim_core(
                spv, pv, x1, w1, a1, rtol, n_events)
            J = jnp.where(finished, jnp.sum(w1 * T), jnp.inf)
            return T, J, finished, jnp.sum(valid)

        return jax.vmap(one)(x, w, arr, sp_b, pol_b)

    return fn


# ---------------------------------------------------------------------------
# Sharded batched planning
# ---------------------------------------------------------------------------

def plan_sharded(
    sp,
    X,
    W,
    B=None,
    active=None,
    *,
    mesh: Mesh | None = None,
    chunk_size: int | None = None,
    coarse: int = 32,
    descent_iters: int = 40,
    cap_iters: int = 64,
    fast_path: bool | None = None,
    validate: bool = False,
    stol_rel: float | None = None,
) -> BatchedSmartFillSchedule:
    """``smartfill_batched`` with the instance axis sharded over a mesh.

    Same contract and padding convention as ``smartfill_batched`` (see
    ``repro.core.batch``); per-instance speedup parameters — sp leaves
    with leading dimension N — shard alongside their instances.  Extra
    knobs:

      mesh: 1-D device mesh (default: the active mesh context, else all
        local devices).
      chunk_size: global instances per scan step for K ≫ memory sweeps;
        rounded up to a multiple of the device count.  None ⇒ one step.

    Instance-by-instance the computation is identical to the
    single-device path, so results match ``smartfill_batched`` exactly
    (the differential guarantee tests/distributed/test_fleet.py pins).
    Heterogeneous fleets shard too: per-job ``(N, M)`` speedup leaves
    (paper §7) split along their instance axis like any batched leaf,
    and the edge-replicated padding keeps every padded row a valid
    family member.
    """
    Xm, Wm, active, m = _prepare(X, W, active)
    N, M = Xm.shape
    if B is None:
        B = sp.B
    Bv = jnp.broadcast_to(jnp.asarray(B, Xm.dtype), (N,))
    if validate:
        validate_padded_instances(Xm, Wm, m)
    sp = collapse_homogeneous(sp)
    check_axes_unambiguous(sp, N, M, "sp")

    mesh = _resolve_mesh(mesh)
    D = mesh.devices.size
    total, _, _ = _chunk_layout(N, D, chunk_size)
    fast = _fast_ok(sp, N) and fast_path is not False

    split = _SplitLeaves(sp, N)
    batched = (
        _pad_rows(Xm, total, edge=False),
        _pad_rows(Wm, total, edge=False),
        _pad_rows(Bv, total, edge=True),        # a valid budget, masked off
        _pad_rows(m, total, edge=False),        # m = 0 ⇒ inert instance
        tuple(_pad_rows(l, total, edge=True) for l in split.batched),
    )
    fn = _plan_fn(split.key, coarse, descent_iters, cap_iters, fast,
                  stol_rel)
    theta, c, a, d, T, J, J_lin, _, _ = _run_sharded(
        mesh, fn, batched, split.shared, N, chunk_size)
    return BatchedSmartFillSchedule(
        theta=theta, c=c, a=a, durations=d, T=T,
        J=J, J_linear=J_lin, active=active, m=m,
    )


def plan_classes_sharded(
    counts,
    sizes,
    weights,
    sp,
    B=None,
    *,
    mesh: Mesh | None = None,
    chunk_size: int | None = None,
    **kwargs,
):
    """Class-aggregated batched planning, instance axis sharded over a mesh.

    The fleet front door for class aggregates (``core/classes.py``): the
    host-side prep is byte-identical to ``plan_classes_batched`` —
    live-first compaction of the (K, C) class slots, the aggregation
    transform S_c(Θ) = n_c·s_c(Θ/n_c) on the speedup leaves, and the
    per-instance normalized-size order — and the aggregate batch then
    rides ``plan_sharded``.  Instance-by-instance the computation is
    identical to the single-device path, so ``(orders, sched)`` match
    ``plan_classes_batched`` exactly (the differential guarantee
    tests/core/test_classes.py pins under the forced-host-devices mesh).
    μ* precision defaults match ``plan_classes_batched`` for the same
    reason.
    """
    from repro.core.classes import compact_aggregate_batch

    if B is None:
        B = sp.B
    kwargs.setdefault("coarse", 64)
    kwargs.setdefault("descent_iters", 96)
    kwargs.setdefault("stol_rel", 1e-10)
    perm, sp_agg, X, W = compact_aggregate_batch(counts, sizes, weights, sp)
    Xm, Wm, active, m = _prepare(X, W, None)
    sp_agg = collapse_homogeneous(sp_agg)
    check_axes_unambiguous(sp_agg, *Xm.shape, "sp")
    orders, sp_p, Xp, Wp = hetero_order_batch(sp_agg, Xm, Wm, m, B)
    sched = plan_sharded(sp_p, Xp, Wp, B=B, active=active, mesh=mesh,
                         chunk_size=chunk_size, **kwargs)
    orders = np.take_along_axis(perm, orders, axis=1)
    return orders, sched


# ---------------------------------------------------------------------------
# Sharded ensemble simulation
# ---------------------------------------------------------------------------

def simulate_ensemble_sharded(
    sp,
    policies,
    X,
    W,
    arrival=None,
    B=None,
    rtol: float = 1e-12,
    n_events: int | None = None,
    faults=None,
    *,
    mesh: Mesh | None = None,
    chunk_size: int | None = None,
) -> EnsembleResult:
    """``simulate_ensemble`` with the workload axis sharded over a mesh.

    Same contract as ``simulate_ensemble`` (see ``repro.core.simulator``)
    — P policies × K workloads, per-workload sp/policy leaves batch by
    the leading-dim-K convention and shard alongside their workloads.
    Policies stay a Python-level loop (each policy is its own device
    program here, where the single-device runner unrolls them into one);
    workloads partition over ``mesh`` with chunked streaming as in
    ``plan_sharded``.

    ``faults``: optional ``FaultTrace`` (1-D shared, or (K, S)-batched —
    one trace per workload).  Fault arrays broadcast to (K, S+1) and
    shard across the mesh *alongside their workloads*, so a chaos
    ensemble (``core.workloads.sample_fault_traces``) fans out over the
    fleet exactly like the workloads it poisons.  Padded instances are
    inert (no live jobs ⇒ the engine halts before consuming any fault),
    and every policy needs a ``B`` leaf to seed its budget carry.
    """
    X = jnp.asarray(X, dtype=jnp.result_type(float))
    W = jnp.asarray(W, dtype=X.dtype)
    if X.ndim != 2 or W.shape != X.shape:
        raise ValueError("X and W must both be (K, M)")
    K, M = X.shape
    _validate_workload(X, W, arrival, what="simulate_ensemble_sharded")
    _validate_budget(B, "simulate_ensemble_sharded")
    ARR = (jnp.zeros_like(X) if arrival is None
           else jnp.asarray(arrival, X.dtype))
    if ARR.shape != X.shape:
        raise ValueError("arrival must be (K, M)")
    policies = tuple(policies)
    if not policies:
        raise ValueError("need at least one policy")
    names = tuple(getattr(p, "name", type(p).__name__) for p in policies)
    if M == 0:
        Pn = len(policies)
        return EnsembleResult(
            J=jnp.zeros((Pn, K), X.dtype), T=jnp.zeros((Pn, K, 0), X.dtype),
            finished=jnp.ones((Pn, K), bool),
            n_events=jnp.zeros((Pn, K), jnp.int32),
            exhausted=jnp.zeros((Pn, K), bool), policy_names=names)
    check_axes_unambiguous(sp, K, M, "sp")
    for p in policies:
        if not getattr(p, "device_ready", False):
            raise ValueError(
                f"policy {p!r} is not device-ready; use sched/policies.py")
        _check_policy_budget(p, B)
        _validate_budget(getattr(p, "B", None), "simulate_ensemble_sharded",
                         source=f"policy {getattr(p, 'name', p)!r}.B")
        check_axes_unambiguous(p, K, M, f"policy {getattr(p, 'name', p)!r}")
    flt = None
    if faults is not None:
        for p in policies:
            _fault_B0(p, None, "simulate_ensemble_sharded")
        flt = _prepared_faults(faults, M, X.dtype, K=K)
        n_events = int(n_events or _fault_n_events(M, faults.S))
    else:
        n_events = int(n_events or n_events_for(M))
    rtol = jnp.asarray(rtol, X.dtype)

    mesh = _resolve_mesh(mesh)
    D = mesh.devices.size
    total, _, _ = _chunk_layout(K, D, chunk_size)
    sp_split = _SplitLeaves(sp, K)
    Xp = _pad_rows(X, total, edge=False)     # size-0 jobs: inert instance
    Wp = _pad_rows(W, total, edge=False)
    ARRp = _pad_rows(ARR, total, edge=False)
    sp_bat = tuple(_pad_rows(l, total, edge=True) for l in sp_split.batched)
    if flt is not None:
        # edge-replicated rows stay valid sorted traces; padded instances
        # have no live jobs, so the engine halts before consuming them
        flt = tuple(_pad_rows(l, total, edge=True) for l in flt)

    Js, Ts, fins, nev = [], [], [], []
    for pol in policies:
        pol_split = _SplitLeaves(pol, K)
        pol_bat = tuple(_pad_rows(l, total, edge=True)
                        for l in pol_split.batched)
        batched = ((Xp, Wp, ARRp, sp_bat, pol_bat) if flt is None
                   else (Xp, Wp, ARRp, sp_bat, pol_bat, flt))
        shared = (sp_split.shared, pol_split.shared, rtol)
        fn = _sim_fn(sp_split.key, pol_split.key, n_events,
                     faulted=flt is not None)
        T, J, finished, ne = _run_sharded(mesh, fn, batched, shared, K,
                                          chunk_size)
        Ts.append(T)
        Js.append(J)
        fins.append(finished)
        nev.append(ne)
    finished_all = jnp.stack(fins)
    nev_all = jnp.stack(nev)
    exhausted = (~finished_all) & (nev_all >= n_events)
    _warn_event_budget(exhausted, n_events, "simulate_ensemble_sharded")
    return EnsembleResult(J=jnp.stack(Js), T=jnp.stack(Ts),
                          finished=finished_all, n_events=nev_all,
                          exhausted=exhausted, policy_names=names)


# ---------------------------------------------------------------------------
# Sharded multi-tenant streaming service
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FleetStreamResult:
    """T tenant streams serviced on the mesh, plus the cross-tenant view.

    ``results[i]`` is tenant i's full ``StreamResult`` (identical in
    meaning to a solo ``StreamController.run_device``).  The remaining
    fields are the fleet-level admission view — the summary a host
    admission/budget controller reads *across* tenants at the horizon:

      backlog: (T,) jobs still unfinished (live slots + FIFO queue).
      unfinished_work: (T,) remaining size mass (partial progress of
        live jobs counted, queued jobs at full size).
      mean_slowdown / p99_latency / deadline_misses: (T,) per-tenant
        SLO columns lifted out of the per-tenant metrics.
      suggested_budget_share: (T,) sums to 1 — unfinished work,
        normalized; the proportional-fair advisory split of the next
        planning round's global budget (uniform when the fleet drained).
    """

    results: tuple
    backlog: np.ndarray
    unfinished_work: np.ndarray
    mean_slowdown: np.ndarray
    p99_latency: np.ndarray
    deadline_misses: np.ndarray
    suggested_budget_share: np.ndarray

    def __len__(self) -> int:
        return len(self.results)


@functools.lru_cache(maxsize=256)
def _serve_fn(lad_key, fast: bool, coarse: int, descent_iters: int,
              cap_iters: int, stol_rel, search_steps: int):
    """Cached tenant-map for stream service (cf. ``_plan_fn``).

    The per-device body runs its local tenants through ``lax.map`` —
    *sequentially*, one full event scan each — rather than ``vmap``:
    under vmap every ``lax.cond`` in the event step lowers to a select
    that executes both branches, so each tenant would pay the full
    cascade solve + exchange search on every event including the inert
    ones.  Sequential tenants keep the real branching; with T a
    multiple of the device count each device carries T/D scans.
    """
    from repro.serve.stream import _stream_event

    knobs = dict(fast=fast, coarse=coarse, descent_iters=descent_iters,
                 cap_iters=cap_iters, stol_rel=stol_rel,
                 search_steps=search_steps)

    def fn(sl, shared):
        state, events, x, w, Bk, lad_b = sl
        sp, lad_sh, plan_latency, rtol, cert_rtol = shared

        def one(args):
            st, ev, x1, w1, B1, lb1 = args
            ladder = _merge_leaves(lad_key, lb1, lad_sh)

            def step(s, e):
                return _stream_event(
                    s, e, sp, ladder, x1, w1, B1, plan_latency, rtol,
                    cert_rtol, knobs), None

            st, _ = lax.scan(step, st, ev)
            return st

        return lax.map(one, (state, events, x, w, Bk, lad_b))

    return fn


def serve_streams_sharded(
    sp,
    streams,
    *,
    budgets=None,
    max_live: int = 16,
    mesh: Mesh | None = None,
    chunk_size: int | None = None,
    plan_latency: float = 0.0,
    rtol: float = 1e-12,
    certificate_rtol: float = 1e-8,
    coarse: int = 32,
    descent_iters: int = 40,
    cap_iters: int = 64,
    stol_rel: float | None = None,
    search_steps: int | None = None,
) -> FleetStreamResult:
    """T independent tenant streams serviced on device, tenant axis
    sharded over the mesh.

    Each tenant is one ``ArrivalStream`` driven through the same traced
    event scan as ``StreamController.run_device`` — cascade replanning,
    double-buffered plans, FIFO queue, cut-at-first-completion backfill
    — under its own nominal budget (trace budget events still override
    live).  Tenants are independent streams, so the shard_map body is
    collective-free and tenant i's result is bit-identical to a solo
    ``run_device`` of the same stream (the parity
    tests/distributed/test_fleet.py pins).

    Padding reuses the fleet contract end to end: tenant rows pad to
    the mesh multiple with zeros, and the device event encoding makes
    an all-zero row *inert* (kind 0 = pad event, no-op on any carry),
    so padded tenants cost one skipped scan each; event/job axes pad to
    the fleet maxima the same way.  Speedup is shared fleet-wide (a
    per-tenant ``sp`` would recompile per tenant — run separate fleets
    instead); ``budgets`` is the per-tenant nominal budget vector
    (default: ``sp.B`` for every tenant), which also seeds each
    tenant's ladder fallback.

    Returns a ``FleetStreamResult``: per-tenant ``StreamResult``s plus
    the cross-tenant admission view (backlog, unfinished work, SLO
    columns, and the advisory ``suggested_budget_share``).

    Spans as ``StreamController.run_device``'s: one ``repro.scan`` with
    ``.prepare``, ``.wait`` and ``.finish``; the tenants' work counters
    summed into ``repro.scan.<name>``.
    """
    from repro.serve.stream import _read_scan

    with obs.span("repro.scan"):
        with obs.span("repro.scan.prepare"):
            streams = tuple(streams)
            sp = collapse_homogeneous(sp)
            if budgets is None:
                budgets = [float(sp.B)] * len(streams)
            budgets = [float(b) for b in budgets]
            out = _dispatch_streams(
                sp, streams, budgets, max_live, mesh, chunk_size,
                plan_latency, rtol, certificate_rtol, coarse,
                descent_iters, cap_iters, stol_rel, search_steps)
        with obs.span("repro.scan.wait"):
            out = jax.block_until_ready(out)
        with obs.span("repro.scan.finish"):
            counters, got = _read_scan(
                out, ("rem", "active", "qbuf", "qhead", "qtail"))
            return _fleet_result(sp, streams, budgets, max_live,
                                 plan_latency, rtol, counters, got)


def _dispatch_streams(sp, streams, budgets, max_live, mesh, chunk_size,
                      plan_latency, rtol, certificate_rtol, coarse,
                      descent_iters, cap_iters, stol_rel, search_steps):
    """``serve_streams_sharded``'s host side up to the dispatch: checks,
    event arrays, initial states, ladders and padding; returns the
    tenants' final scan states (not waited for)."""
    from repro.robust.degrade import DegradingPolicy
    from repro.serve.stream import _event_arrays, _stream_state0

    T = len(streams)
    if T < 1:
        raise ValueError("need at least one tenant stream")
    if any(getattr(l, "ndim", 0) >= 1
           for l in jax.tree_util.tree_leaves(sp)):
        raise ValueError(
            "serve_streams_sharded needs one shared scalar-leaf speedup; "
            "per-tenant speedups belong in separate fleets")
    M = int(max_live)
    if M < 1:
        raise ValueError("max_live must be >= 1")
    dtype = jnp.result_type(float)
    if len(budgets) != T:
        raise ValueError("budgets must give one nominal budget per tenant")

    Ns = [len(s) for s in streams]
    Nmax = max(1, max(Ns))
    evs = [_event_arrays(s) for s in streams]
    Emax = max(e[0].size for e in evs)
    t_e = np.zeros((T, Emax))
    kind = np.zeros((T, Emax), np.int32)
    pi = np.zeros((T, Emax), np.int32)
    pf = np.zeros((T, Emax))
    for i, (te, kd, pj, pv) in enumerate(evs):
        t_e[i, :te.size] = te
        kind[i, :te.size] = kd
        pi[i, :te.size] = pj
        pf[i, :te.size] = pv
    X = np.zeros((T, Nmax))
    W = np.zeros((T, Nmax))
    for i, strm in enumerate(streams):
        X[i, :Ns[i]] = np.asarray(strm.x, float)
        W[i, :Ns[i]] = np.asarray(strm.w, float)

    state = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls),
        *[_stream_state0(M, Nmax, budgets[i], dtype) for i in range(T)])
    ladders = [DegradingPolicy.ladder(sp, B=b) for b in budgets]
    lad_st = jax.tree_util.tree_map(
        lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]), *ladders)
    lad_split = _SplitLeaves(lad_st, T)

    mesh = _resolve_mesh(mesh)
    D = mesh.devices.size
    total, _, _ = _chunk_layout(T, D, chunk_size)
    batched = (
        jax.tree_util.tree_map(
            lambda l: _pad_rows(l, total, edge=False), state),
        tuple(_pad_rows(jnp.asarray(a), total, edge=False)
              for a in (t_e, kind, pi, pf)),
        _pad_rows(jnp.asarray(X, dtype), total, edge=False),
        _pad_rows(jnp.asarray(W, dtype), total, edge=False),
        _pad_rows(jnp.asarray(budgets, dtype), total, edge=True),
        tuple(_pad_rows(l, total, edge=True) for l in lad_split.batched),
    )
    shared = (sp, lad_split.shared, jnp.asarray(plan_latency, dtype),
              jnp.asarray(rtol, dtype), jnp.asarray(certificate_rtol, dtype))
    fn = _serve_fn(lad_split.key, _fast_ok(sp), int(coarse),
                   int(descent_iters), int(cap_iters), stol_rel,
                   4 * M if search_steps is None else int(search_steps))
    # four event arrays, x, w, budgets, three shared scalars, and the
    # host leaves of the speedup and the ladders
    obs.count("repro.h2d", 10 + obs.host_leaves(sp, ladders))
    return _run_sharded(mesh, fn, batched, shared, T, chunk_size)


def _fleet_result(sp, streams, budgets, max_live, plan_latency, rtol,
                  counters, got) -> FleetStreamResult:
    """Per-tenant results and the admission view from the one read of
    the tenants' final states."""
    from repro.serve.stream import StreamController

    T = len(streams)
    M = int(max_live)
    comp_all = np.asarray(got["completion"], float)
    rem = np.asarray(got["rem"], float)
    act = np.asarray(got["active"], bool)
    qb, qh, qt = got["qbuf"], got["qhead"], got["qtail"]
    results = []
    backlog = np.zeros(T, int)
    work = np.zeros(T)
    for i, strm in enumerate(streams):
        n = len(strm)
        ctl = StreamController(sp, budgets[i], max_live=M,
                               plan_latency=plan_latency, rtol=rtol)
        results.append(ctl._finalize(
            strm, comp_all[i, :n], np.ones(n, bool),
            **{k: v[i] for k, v in counters.items()}))
        qidx = qb[i, qh[i]:qt[i]]
        backlog[i] = int(act[i].sum()) + qidx.size
        work[i] = float(np.sum(rem[i] * act[i]))
        if qidx.size:
            work[i] += float(np.sum(np.asarray(strm.x, float)[qidx]))
    share = (work / work.sum() if work.sum() > 0
             else np.full(T, 1.0 / T))
    return FleetStreamResult(
        results=tuple(results),
        backlog=backlog,
        unfinished_work=work,
        mean_slowdown=np.array([r.metrics.mean_slowdown for r in results]),
        p99_latency=np.array([r.metrics.p99_latency for r in results]),
        deadline_misses=np.array([r.metrics.deadline_misses
                                  for r in results]),
        suggested_budget_share=share,
    )
