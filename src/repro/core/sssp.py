"""The heuristic SSSP algorithm (paper §3.3, Algorithm 2 + Function 1/2).

Single-device, fully jitted reference engine.  The control flow is flattened
into one ``lax.while_loop`` whose body executes one *round* of edge
relaxations; when the frontier empties, the same iteration performs the step
transition (Function 2's ``computeST``, the dynamic-stepping ``gap``, and
Function 1's ``initFrontiers`` including the pull phase).

The windowed relaxation itself (Algo 2 l.8-17) is delegated to a pluggable
backend from :mod:`repro.core.relax` — ``segment_min`` (dense flat edge
list) or ``blocked_pallas`` (the ``BlockedGraph`` layout driving the
``kernels/edge_relax`` Pallas kernel).  All backends resolve ties
deterministically (min candidate, then min source id), so results and
logical-traversal metrics are identical across them.

TPU-native adaptation (DESIGN.md §2): the MPI worklist becomes a dense
frontier mask + masked edge-parallel relaxation with a deterministic
``segment_min`` replacing the CAS; per-round metrics count *logical*
traversals exactly as the paper defines them (the weight-sorted adjacency +
binary search of the C implementation touches precisely the edges our masks
enable).

Three deliberate, documented deviations:
  * ``nFrontier`` counts successful non-leaf dist updates (every SAP-pushed
    vertex is popped exactly once per update, and leaf pops are pruned), plus
    one for the source pop — equal to worklist pops in the MPI original.
  * Empty-window fast-forward: when a step transition finds no pending path
    length inside the next window, ``lb`` snaps to the smallest pending
    length (exact — no shortest path can exist in the skipped range).  This
    also yields the termination test (no pending candidate ⇒ done), which is
    equivalent to line 23 of Algorithm 2 but robust to disconnected graphs.
  * Pull-phase ``n_relax`` counts requests as *created* on the responder
    side (``dist[resp] in [st, lb)`` with an in-window candidate), matching
    the MPI model where the owner sends REQUEST messages without knowing
    whether the requester is still unsettled.  This makes the counter
    computable identically by the sharded engines (the requester's dist is
    remote there).
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from . import relax, stats, stepping, traversal
from .config import (P2P_MODES, ConfigError, EngineConfig,
                     FacadeDeprecationWarning, as_resolved)
from .graph import DeviceGraph
from .relax import INF, INT_MAX
from ..obs import profiling
from ..obs.trace import trace_append, trace_init

__all__ = ["sssp", "sssp_batch", "sssp_p2p", "sssp_bounded", "sssp_knear",
           "repair_relax", "compiled_text",
           "SsspMetrics", "LOGICAL_METRIC_FIELDS", "PHYSICAL_METRIC_FIELDS",
           "metrics_dict", "normalized_metrics",
           "GOALS", "goal_param_array", "INF", "INT_MAX"]

# Early-exit query goals.  A goal turns the full shortest-path-tree
# computation into a query that terminates as soon as its answer is
# settled (the stepping invariant: every vertex with dist < lb is final,
# see relax.settled_mask), saving the remaining windows entirely:
#
#   "tree"    — no goal; run until every reachable vertex settles.
#   "p2p"     — point-to-point: stop once `target` (the goal param) is
#               settled; dist[target]/the parent chain back to the source
#               are then bitwise-equal to the full-tree result.
#   "bounded" — distance-bounded search: stop once lb > D, i.e. every
#               vertex with dist <= D is settled.
#   "knear"   — k-nearest: stop once k+1 vertices (the source plus its k
#               nearest) are settled.
#
# The goal kind is static (part of the jit cache key); the goal parameter
# is a traced scalar (int32 target/k, float32 bound) so one compiled
# engine serves every target/bound/k — and vmaps over per-source params
# in sssp_batch.
GOALS = ("tree", "p2p", "bounded", "knear")


def goal_param_array(goal: str, params) -> jnp.ndarray:
    """Coerce goal parameter(s) to the dtype the engine expects."""
    if goal not in GOALS:
        raise ValueError(f"unknown goal {goal!r}; expected one of {GOALS}")
    if goal == "tree":
        shape = () if params is None or jnp.ndim(params) == 0 \
            else (len(params),)
        return jnp.zeros(shape, jnp.int32)
    if params is None:
        raise ValueError(f"goal {goal!r} requires a parameter "
                         "(target / bound / k)")
    dtype = jnp.float32 if goal == "bounded" else jnp.int32
    return jnp.asarray(params, dtype)


def _check_goal_bounds(goal: str, gp, n: int) -> None:
    """Reject out-of-range p2p targets while they are still concrete: a
    jit gather clamps silently, which would report vertex n-1's distance
    as the target's.  Traced params (calls from inside jit) are skipped —
    the caller owns validation there."""
    if goal != "p2p":
        return
    try:
        t = np.asarray(gp)
    except Exception:
        return
    if t.size and (int(t.min()) < 0 or int(t.max()) >= n):
        raise ValueError(f"p2p target(s) {t} out of range for graph "
                         f"with n={n}")


def _goal_reached(goal: str, goal_param, dist, lb):
    """Whether the query goal is settled at window lower bound ``lb``."""
    if goal == "tree":
        return jnp.bool_(False)
    if goal == "p2p":
        return relax.settled_mask(dist, lb)[goal_param]
    if goal == "bounded":
        return lb > goal_param
    if goal == "knear":
        n_settled = jnp.sum(relax.settled_mask(dist, lb).astype(jnp.int32))
        return n_settled >= goal_param + 1
    raise ValueError(f"unknown goal {goal!r}; expected one of {GOALS}")


class SsspMetrics(NamedTuple):
    n_rounds: jnp.ndarray      # synchronized relaxation rounds ("nSync" raw)
    n_steps: jnp.ndarray       # scheduling-threshold pairs constructed
    n_extended: jnp.ndarray    # extended paths ("nFrontier" raw)
    n_trav: jnp.ndarray        # edge traversals, push model ("nTrav" raw part)
    n_pull_trav: jnp.ndarray   # edge traversals, pull model (requests)
    n_relax: jnp.ndarray       # relaxation attempts (created paths)
    n_updates: jnp.ndarray     # successful relaxations (dist improvements)
    n_pruned: jnp.ndarray      # candidates cut by the ALT goal-directed bound
    n_tiles_scanned: jnp.ndarray  # blocked layouts: tiles actually run (f32)
    n_tiles_dense: jnp.ndarray    # blocked layouts: dense-grid cost (f32)
    n_invocations: jnp.ndarray    # kernel launches / sync units (f32)
    n_compact_rounds: jnp.ndarray  # rounds that ran compacted (f32)


# The *physical* counters: layout/launch geometry (0 outside blocked
# layouts) and the compacted rounds (0 outside the unbatched segment_min
# solve), excluded from cross-backend/engine parity checks.  Everything
# else is logical and must agree bitwise across backends and tiers.
PHYSICAL_METRIC_FIELDS = ("n_tiles_scanned", "n_tiles_dense",
                          "n_invocations", "n_compact_rounds")
LOGICAL_METRIC_FIELDS = tuple(f for f in SsspMetrics._fields
                              if f not in PHYSICAL_METRIC_FIELDS)


class SsspState(NamedTuple):
    dist: jnp.ndarray
    parent: jnp.ndarray
    frontier: jnp.ndarray
    lb: jnp.ndarray
    ub: jnp.ndarray
    st: jnp.ndarray
    done: jnp.ndarray
    iters: jnp.ndarray
    metrics: SsspMetrics


def _zero_metrics() -> SsspMetrics:
    z = jnp.int32(0)
    f = jnp.float32(0)      # physical counters accumulate past int32 range
    return SsspMetrics(**{name: f if name in PHYSICAL_METRIC_FIELDS else z
                          for name in SsspMetrics._fields})


def _relax_round(backend: relax.RelaxBackend, layout, st_: SsspState,
                 alt_lb=None, prune_bound=None, caps=None) -> SsspState:
    """One synchronized round of push-model edge relaxations (Algo 2 l.8-17),
    dispatched through the selected relaxation backend.  ``alt_lb``/
    ``prune_bound`` (p2p with landmarks) enable the ALT goal-directed cut
    inside the relaxation (see :func:`repro.core.relax.alt_prune`).
    ``caps`` (:func:`repro.core.relax.compact_caps`) runs the backend's
    compacted round; None runs its ``relax_window``."""
    with profiling.phase("sssp.round"):
        fn = (backend.relax_window if caps is None
              else partial(backend.relax_compact, caps=caps))
        new_dist, new_parent, rm = fn(
            layout, st_.dist, st_.parent, st_.frontier, st_.lb, st_.ub,
            alt_lb, prune_bound)
        m = st_.metrics
        metrics = m._replace(
            n_rounds=m.n_rounds + jnp.where(jnp.any(st_.frontier), 1, 0),
            n_extended=m.n_extended + rm.n_extended,
            n_trav=m.n_trav + rm.n_trav,
            n_relax=m.n_relax + rm.n_relax,
            n_updates=m.n_updates + rm.n_updates,
            n_pruned=m.n_pruned + rm.n_pruned,
            n_tiles_scanned=m.n_tiles_scanned + rm.n_tiles_scanned,
            n_tiles_dense=m.n_tiles_dense + rm.n_tiles_dense,
            n_invocations=m.n_invocations + rm.n_invocations,
            n_compact_rounds=m.n_compact_rounds + rm.n_compact,
        )
    return st_._replace(dist=new_dist, parent=new_parent,
                        frontier=rm.improved, metrics=metrics)


def _fused_relax_rounds(bg, fs, st_: SsspState, fused_rounds: int,
                        alt_lb=None, prune_ub=None, prune_infl=None,
                        prune_tgt=None) -> SsspState:
    """Up to ``fused_rounds`` synchronized rounds in ONE megakernel
    invocation (blocked layouts only) — the fused twin of calling
    :func:`_relax_round` once per round until the window settles.
    Bitwise-identical dist/parent/frontier and logical counters; the
    kernel folds the counters into its scheduled tile pass and reports
    per-invocation sums (``FUSED_COUNTERS``)."""
    with profiling.phase("sssp.round"):
        new_dist, new_parent, new_front, cnt = relax.blocked_fused_rounds(
            bg, fs, st_.dist, st_.parent, st_.frontier, st_.lb, st_.ub,
            fused_rounds=fused_rounds, alt_lb=alt_lb, prune_ub=prune_ub,
            prune_infl=prune_infl, prune_tgt=prune_tgt)
        m = st_.metrics
        metrics = m._replace(
            n_rounds=m.n_rounds + cnt[4],
            n_trav=m.n_trav + cnt[0],
            n_relax=m.n_relax + cnt[1],
            n_updates=m.n_updates + cnt[2],
            n_extended=m.n_extended + cnt[3],
            n_pruned=m.n_pruned + cnt[7],
            n_tiles_scanned=m.n_tiles_scanned + cnt[5].astype(jnp.float32),
            # the dense-grid comparator charges one full grid per round
            n_tiles_dense=m.n_tiles_dense
            + cnt[6].astype(jnp.float32) * bg.dense_grid_tiles,
            n_invocations=m.n_invocations + jnp.float32(1),
        )
    return st_._replace(dist=new_dist, parent=new_parent,
                        frontier=new_front, metrics=metrics)


def _bootstrap_ub(g: DeviceGraph, st_: SsspState,
                  high_d0: jnp.ndarray) -> SsspState:
    """Algo 2 l.18-20: during the first step, tighten ub to the shortest known
    path linking s to a vertex of degree >= highD(0)."""
    def tighten(ub):
        mask = (g.deg.astype(jnp.float32) >= high_d0) & (st_.dist > 0)
        cand = jnp.min(jnp.where(mask, st_.dist, INF))
        return jnp.minimum(ub, cand)
    with profiling.phase("sssp.bootstrap"):
        ub = jax.lax.cond(st_.lb <= 0.0, tighten, lambda ub: ub, st_.ub)
    return st_._replace(ub=ub)


def _pull_phase(g: DeviceGraph, dist, parent, st, lb, ub, metrics,
                alt_lb=None, prune_bound=None):
    """Function 1's pull phase: settled band [st, lb) answers requests from
    unsettled vertices (built from the shared relax primitives).  Under
    ALT the *requester* (``g.src``) is the vertex receiving the update,
    so requests with ``cand + alt_lb[src] > prune_bound`` are cut."""
    dv = dist[g.dst]
    # edges a pull scan touches: requester unsettled, weight short enough
    scan = (dist[g.src] > lb) & (g.w < ub - st)
    # requests created (responder side; w < ub - st is implied)
    mask = (dv >= st) & (dv < lb) & (dv + g.w < ub)
    cand = jnp.where(mask, dv + g.w, INF)
    n_pruned = jnp.int32(0)
    if alt_lb is not None:
        mask, pruned = relax.alt_prune(cand, mask, alt_lb[g.src],
                                       prune_bound)
        cand = jnp.where(mask, cand, INF)
        n_pruned = jnp.sum(pruned.astype(jnp.int32))
    best, winner = relax.segment_min_with_winner(cand, mask, g.dst, g.src,
                                                 g.n)
    new_dist, new_parent, improved = relax.apply_updates(
        dist, parent, best, winner, gate=dist > lb)
    nonleaf_upd = improved & (g.deg > 1)
    metrics = metrics._replace(
        n_pull_trav=metrics.n_pull_trav + jnp.sum(scan.astype(jnp.int32)),
        n_extended=metrics.n_extended +
        jnp.sum(nonleaf_upd.astype(jnp.int32)),
        n_relax=metrics.n_relax + jnp.sum(mask.astype(jnp.int32)),
        n_updates=metrics.n_updates + jnp.sum(improved.astype(jnp.int32)),
        n_pruned=metrics.n_pruned + n_pruned,
        n_rounds=metrics.n_rounds + 1,  # the pull phase is a round/sync
    )
    return new_dist, new_parent, metrics


def _transition(g: DeviceGraph, st_: SsspState,
                params: stepping.SteppingParams, goal: str,
                goal_param, ps: stepping.PolicyState = None,
                alt_lb=None, bound_of=None):
    """Step transition (Algo 2 l.22 + Function 1/2 + fast-forward/termination).

    With the adaptive policy, ``ps`` carries the traced
    :class:`~repro.core.stepping.PolicyState`: the transition first folds
    the counters observed since the previous step into it (observe →
    adapt), then sizes the next window from the adapted parameters, and
    returns ``(state, ps)``.  ``ps is None`` (static policy) compiles the
    exact pre-policy program and returns the state alone.
    """
    with profiling.phase("sssp.transition"):
        dist, parent = st_.dist, st_.parent
        lb, ub = st_.lb, st_.ub

        with profiling.phase("transition.pending"):
            # smallest pending candidate path length (>= ub); inf <=> done
            pend = dist[g.src] + g.w
            pend = jnp.where(pend >= ub, pend, INF)
            if alt_lb is not None:
                # a pending candidate the ALT bound would cut can never
                # improve the goal vertex, so it neither blocks
                # termination nor anchors the fast-forward: skipping it
                # is exact for the p2p contract
                bound_eff = bound_of(dist)
                pend = jnp.where(pend + alt_lb[g.dst] > bound_eff, INF,
                                 pend)
            min_pending = jnp.min(pend)
        done = ~jnp.isfinite(min_pending)

        if ps is not None:
            m = st_.metrics
            ps = stepping.adaptive_update(ps, m.n_rounds, m.n_relax,
                                          m.n_updates)
            params = stepping.effective_params(ps)
            mult = ps.mult
        else:
            mult = None
        with profiling.phase("transition.window"):
            st_next = traversal.compute_st(dist, g.deg, g.rtow,
                                           g.n_edges2, lb, ub, params,
                                           mult=mult)
            lb2 = ub
            gap2 = stepping.gap(dist, g.deg, g.rtow, g.n_edges2, lb2,
                                params, mult)
            ub2 = lb2 + gap2
            # empty-window fast-forward (exact; see module docstring)
            ffwd = (min_pending >= ub2) & ~done
            lb2 = jnp.where(ffwd, min_pending, lb2)
            gap3 = stepping.gap(dist, g.deg, g.rtow, g.n_edges2, lb2,
                                params, mult)
            ub2 = jnp.where(ffwd, lb2 + gap3, ub2)
            st_next = jnp.minimum(st_next, lb2)

        def with_pull(args):
            dist, parent, metrics = args
            return _pull_phase(g, dist, parent, st_next, lb2, ub2, metrics,
                               alt_lb,
                               None if alt_lb is None else bound_eff)

        with profiling.phase("transition.pull"):
            dist, parent, metrics = jax.lax.cond(
                st_next < lb2, with_pull, lambda a: a,
                (dist, parent, st_.metrics))
        # early-exit goal: the settled set only grows at step
        # transitions, so checking here is exact — and costs one
        # reduction per transition.
        done = done | _goal_reached(goal, goal_param, dist, lb2)
        frontier = relax.window_frontier(dist, st_next, lb2, ub2,
                                         g.rtow[-1])
        frontier = frontier & ~done
        metrics = metrics._replace(
            n_steps=metrics.n_steps + jnp.where(done, 0, 1))
        out = st_._replace(dist=dist, parent=parent, frontier=frontier,
                           lb=lb2, ub=ub2, st=st_next, done=done,
                           metrics=metrics)
        return out if ps is None else (out, ps)


def _trace_record(s0: SsspState, s1: SsspState, buf):
    """Append one per-iteration trace record to ``buf`` (inside jit).

    ``s0``/``s1`` are the loop state before/after the body, so every
    counter column is the exact int32 delta the iteration contributed —
    the host-side ``SolveTrace.counter_sums`` parity contract
    (:mod:`repro.obs.trace`).  Reads state only: dist/parent/metrics
    stay bitwise-identical with tracing on.
    """
    m0, m1 = s0.metrics, s1.metrics
    # the transition ran iff it advanced a step (or terminated the solve)
    stepped = ((m1.n_steps > m0.n_steps) | (s1.done & ~s0.done))
    ivals = {
        "iter": s0.iters,
        "frontier": jnp.sum(s0.frontier.astype(jnp.int32)),
        "stepped": stepped.astype(jnp.int32),
        "n_rounds": m1.n_rounds - m0.n_rounds,
        "n_steps": m1.n_steps - m0.n_steps,
        "n_extended": m1.n_extended - m0.n_extended,
        "n_trav": m1.n_trav - m0.n_trav,
        "n_pull_trav": m1.n_pull_trav - m0.n_pull_trav,
        "n_relax": m1.n_relax - m0.n_relax,
        "n_updates": m1.n_updates - m0.n_updates,
        "n_pruned": m1.n_pruned - m0.n_pruned,
    }
    fvals = {
        "lb": s0.lb, "ub": s0.ub, "st": s0.st,
        "n_tiles_scanned": m1.n_tiles_scanned - m0.n_tiles_scanned,
        "n_tiles_dense": m1.n_tiles_dense - m0.n_tiles_dense,
        "n_invocations": m1.n_invocations - m0.n_invocations,
        "n_compact_rounds": m1.n_compact_rounds - m0.n_compact_rounds,
    }
    return trace_append(buf, ivals, fvals)


def _run(g: DeviceGraph, layout, source, backend: relax.RelaxBackend,
         max_iters: int, alpha: float, beta: float, goal: str = "tree",
         goal_param=None, fused_rounds: int = 0, fused=None,
         trace_capacity: int = 0, policy: str = "static",
         alt_data=None, p2p_mode: str = "unidirectional", caps=None):
    """Trace one SSSP computation (shared by sssp / sssp_batch); ``goal``
    selects the early-exit variant (see GOALS).  ``fused_rounds > 0``
    (blocked layouts only) runs each window's rounds through the fused
    megakernel — one kernel invocation per up-to-``fused_rounds`` rounds
    instead of one per source block per round; ``fused`` carries the
    prebuilt :class:`~repro.core.relax.FusedSlab` so the concatenation
    is hoisted out of vmapped batches.  ``trace_capacity > 0`` records a
    per-round :class:`~repro.obs.trace.TraceBuf` ring (returned as a
    fourth output; ``None`` otherwise) — the knob is static, so 0
    compiles the exact untraced program.  ``policy`` is static too:
    ``"static"`` compiles the exact pre-policy program, ``"adaptive"``
    carries a :class:`~repro.core.stepping.PolicyState` in the loop and
    re-sizes the window at each step transition.  ``caps`` (static; see
    :func:`repro.core.relax.compact_caps`) runs the backend's compacted
    rounds, and only an unbatched solve passes them: under ``vmap``
    every branch of their switch would run."""
    params = stepping.SteppingParams(alpha=alpha, beta=beta)
    adaptive = policy == "adaptive"
    if policy not in stepping.POLICIES:
        raise ConfigError(f"unknown policy {policy!r}; expected one of "
                          f"{stepping.POLICIES}")
    if fused_rounds > 0:
        if not isinstance(layout, relax.BlockedGraph):
            raise ConfigError(
                "fused_rounds needs a blocked layout on the single-device "
                f"tier; got {type(layout).__name__} (set a blocked "
                "backend, or drop fused_rounds)")
        if fused is None:
            fused = relax.fused_slab(layout)
    if goal_param is None:
        goal_param = jnp.int32(0)
    if p2p_mode not in P2P_MODES:
        raise ConfigError(f"unknown p2p_mode {p2p_mode!r}; expected one "
                          f"of {P2P_MODES}")
    n = g.n
    source = jnp.asarray(source, jnp.int32)
    alt = alt_data is not None and goal == "p2p"
    if goal == "p2p" and p2p_mode == "bidirectional":
        if not alt:
            raise ConfigError("p2p_mode='bidirectional' needs a landmark "
                              "set (use_alt=True / landmarks=...)")
        if adaptive or trace_capacity > 0:
            raise ConfigError("p2p_mode='bidirectional' supports only "
                              "policy='static' without tracing")
        return _run_bidi(g, layout, source, backend, max_iters, params,
                         goal_param, fused_rounds, fused, alt_data, caps)
    if alt:
        tgt = jnp.asarray(goal_param, jnp.int32)
        alt_lb = relax.alt_lower_bounds(alt_data.D, tgt, alt_data.delta,
                                        alt_data.sym)
        infl = 1.0 + 4.0 * alt_data.delta
        prune_ub = relax.alt_seed_ub(alt_data.D, source, tgt, infl,
                                     alt_data.sym)
        # best-known s->t length this round, inflated so the engine's own
        # f32 path sums always survive the cut (see relax.py)
        bound_of = lambda dist: jnp.minimum(prune_ub, dist[tgt] * infl)
    else:
        alt_lb = bound_of = None
    dist0 = jnp.full((n,), INF, jnp.float32).at[source].set(0.0)
    parent0 = jnp.full((n,), -1, jnp.int32).at[source].set(source)
    frontier0 = jnp.zeros((n,), bool).at[source].set(True)
    high_d0 = stats.high_d(jnp.zeros((n,), jnp.float32), g.deg,
                           jnp.float32(0.0))

    # the source's own pop is the first extended path
    metrics0 = _zero_metrics()._replace(n_extended=jnp.int32(1))
    init = SsspState(dist=dist0, parent=parent0, frontier=frontier0,
                     lb=jnp.float32(0.0), ub=INF, st=jnp.float32(0.0),
                     done=jnp.bool_(False), iters=jnp.int32(0),
                     metrics=metrics0)

    def cond(s: SsspState):
        return (~s.done) & (s.iters < max_iters)

    def relax_step(s: SsspState) -> SsspState:
        if fused_rounds > 0:
            if alt:
                return _fused_relax_rounds(layout, fused, s, fused_rounds,
                                           alt_lb, prune_ub, infl, tgt)
            return _fused_relax_rounds(layout, fused, s, fused_rounds)
        if alt:
            return _relax_round(backend, layout, s, alt_lb,
                                bound_of(s.dist), caps)
        return _relax_round(backend, layout, s, caps=caps)

    def body(s: SsspState):
        s = relax_step(s)
        s = _bootstrap_ub(g, s, high_d0)
        s = jax.lax.cond(jnp.any(s.frontier),
                         lambda x: x,
                         lambda x: _transition(g, x, params, goal,
                                               goal_param, alt_lb=alt_lb,
                                               bound_of=bound_of),
                         s)
        return s._replace(iters=s.iters + 1)

    def body_adaptive(carry):
        s, ps = carry
        s = relax_step(s)
        s = _bootstrap_ub(g, s, high_d0)
        s, ps = jax.lax.cond(jnp.any(s.frontier),
                             lambda c: c,
                             lambda c: _transition(g, c[0], params, goal,
                                                   goal_param, ps=c[1],
                                                   alt_lb=alt_lb,
                                                   bound_of=bound_of),
                             (s, ps))
        return s._replace(iters=s.iters + 1), ps

    if not adaptive:
        if trace_capacity <= 0:
            out = jax.lax.while_loop(cond, body, init)
            return out.dist, out.parent, out.metrics, None

        def traced_body(carry):
            s, buf = carry
            s1 = body(s)
            return s1, _trace_record(s, s1, buf)

        out, buf = jax.lax.while_loop(lambda c: cond(c[0]), traced_body,
                                      (init, trace_init(trace_capacity)))
        return out.dist, out.parent, out.metrics, buf

    init_a = (init, stepping.policy_init(params))
    if trace_capacity <= 0:
        out, _ = jax.lax.while_loop(lambda c: cond(c[0]), body_adaptive,
                                    init_a)
        return out.dist, out.parent, out.metrics, None

    def traced_adaptive(carry):
        c, buf = carry
        c1 = body_adaptive(c)
        return c1, _trace_record(c[0], c1[0], buf)

    (out, _), buf = jax.lax.while_loop(lambda c: cond(c[0][0]),
                                       traced_adaptive,
                                       (init_a, trace_init(trace_capacity)))
    return out.dist, out.parent, out.metrics, buf


def _run_bidi(g: DeviceGraph, layout, source, backend, max_iters,
              params: stepping.SteppingParams, target, fused_rounds, fused,
              alt_data, caps=None):
    """Bidirectional meet-in-the-middle p2p (goal="p2p" only).

    A forward solve (from ``source``) and a backward solve (from
    ``target``, over the same symmetric graph) alternate windows —
    whichever side's window lower bound trails advances one iteration.
    Every advance tightens the shared meet bound
    ``mu = min_v dist_f[v] + dist_b[v]`` (a valid s->t path length on a
    symmetric graph), which feeds BOTH sides' ALT prune bounds through
    ``min(seed_ub, mu * infl)`` — strictly more pruning pressure than
    either side alone.

    The forward solve stays *authoritative*: it terminates by the
    standard p2p criterion (target settled), and since the extra
    pruning is exact, its ``dist[target]``/parent chain are
    bitwise-identical to the unidirectional solve (mu never finalizes
    values — a mu-based finalize would break the bitwise contract).
    The backward side freezes once its goal settles or
    ``lb_f + lb_b >= mu`` (its windows can no longer tighten mu).
    Metrics are summed over both sides: total work, which is what the
    benchmark comparisons need.
    """
    n = g.n
    target = jnp.asarray(target, jnp.int32)
    D, delta, sym = alt_data.D, alt_data.delta, alt_data.sym
    infl = 1.0 + 4.0 * delta
    lb_f = relax.alt_lower_bounds(D, target, delta, sym)
    lb_b = relax.alt_lower_bounds(D, source, delta, sym)
    seed = relax.alt_seed_ub(D, source, target, infl, sym)
    high_d0 = stats.high_d(jnp.zeros((n,), jnp.float32), g.deg,
                           jnp.float32(0.0))

    def init_state(s):
        return SsspState(
            dist=jnp.full((n,), INF, jnp.float32).at[s].set(0.0),
            parent=jnp.full((n,), -1, jnp.int32).at[s].set(s),
            frontier=jnp.zeros((n,), bool).at[s].set(True),
            lb=jnp.float32(0.0), ub=INF, st=jnp.float32(0.0),
            done=jnp.bool_(False), iters=jnp.int32(0),
            metrics=_zero_metrics()._replace(n_extended=jnp.int32(1)))

    def side_body(s, alt_lb_s, goal_v, mu):
        ub_eff = jnp.minimum(seed, mu * infl)
        bound_of = lambda dist: jnp.minimum(ub_eff, dist[goal_v] * infl)
        if fused_rounds > 0:
            s = _fused_relax_rounds(layout, fused, s, fused_rounds,
                                    alt_lb_s, ub_eff, infl, goal_v)
        else:
            s = _relax_round(backend, layout, s, alt_lb_s,
                             bound_of(s.dist), caps)
        s = _bootstrap_ub(g, s, high_d0)
        s = jax.lax.cond(jnp.any(s.frontier),
                         lambda x: x,
                         lambda x: _transition(g, x, params, "p2p", goal_v,
                                               alt_lb=alt_lb_s,
                                               bound_of=bound_of),
                         s)
        return s._replace(iters=s.iters + 1)

    def cond(c):
        sf, sb, mu = c
        return (~sf.done) & (sf.iters + sb.iters < 2 * max_iters)

    def body(c):
        sf, sb, mu = c
        frozen = sb.done | (sf.lb + sb.lb >= mu)
        fwd = frozen | (sf.lb <= sb.lb)
        sf = jax.lax.cond(fwd, lambda x: side_body(x, lb_f, target, mu),
                          lambda x: x, sf)
        sb = jax.lax.cond(fwd, lambda x: x,
                          lambda x: side_body(x, lb_b, source, mu), sb)
        mu = jnp.minimum(mu, jnp.min(sf.dist + sb.dist))
        return sf, sb, mu

    sf, sb, _mu = jax.lax.while_loop(
        cond, body, (init_state(source), init_state(target), INF))
    metrics = SsspMetrics(*[a + b for a, b in zip(sf.metrics, sb.metrics)])
    return sf.dist, sf.parent, metrics, None


def _compact_caps(backend: relax.RelaxBackend, layout):
    """The caps of ``backend``'s compacted round on ``layout``
    (:func:`repro.core.relax.compact_caps`), or None where it has none."""
    if backend.relax_compact is None:
        return None
    return relax.compact_caps(layout.n, layout.m)


@partial(jax.jit, static_argnames=("backend", "max_iters", "alpha", "beta",
                                   "goal", "fused_rounds", "trace_capacity",
                                   "policy", "p2p_mode"))
def _sssp_jit(g, layout, source, backend, max_iters, alpha, beta, goal,
              goal_param, fused_rounds=0, trace_capacity=0,
              policy="static", alt_data=None, p2p_mode="unidirectional"):
    return _run(g, layout, source, backend, max_iters, alpha, beta, goal,
                goal_param, fused_rounds, trace_capacity=trace_capacity,
                policy=policy, alt_data=alt_data, p2p_mode=p2p_mode,
                caps=_compact_caps(backend, layout))


@partial(jax.jit, static_argnames=("backend", "max_iters", "alpha", "beta",
                                   "goal", "fused_rounds", "trace_capacity",
                                   "policy", "p2p_mode"))
def _sssp_batch_jit(g, layout, sources, backend, max_iters, alpha, beta,
                    goal, goal_params, fused_rounds=0, trace_capacity=0,
                    policy="static", alt_data=None,
                    p2p_mode="unidirectional"):
    # build the fused slab once, outside vmap, so the concatenation isn't
    # replicated per batch slot
    fused = relax.fused_slab(layout) if (
        fused_rounds > 0 and isinstance(layout, relax.BlockedGraph)) \
        else None
    return jax.vmap(
        lambda s, gp: _run(g, layout, s, backend, max_iters, alpha, beta,
                           goal, gp, fused_rounds, fused,
                           trace_capacity=trace_capacity, policy=policy,
                           alt_data=alt_data, p2p_mode=p2p_mode)
    )(sources, goal_params)


@partial(jax.jit, static_argnames=("backend", "max_iters", "fused_rounds",
                                   "caps"))
def _repair_jit(layout, dist0, parent0, frontier0, backend, max_iters,
                fused_rounds, caps=None):
    fused = relax.fused_slab(layout) if fused_rounds > 0 else None
    init = SsspState(dist=dist0, parent=parent0, frontier=frontier0,
                     lb=jnp.float32(0.0), ub=INF, st=jnp.float32(0.0),
                     done=jnp.bool_(False), iters=jnp.int32(0),
                     metrics=_zero_metrics())

    def cond(s: SsspState):
        return jnp.any(s.frontier) & (s.iters < max_iters)

    def body(s: SsspState):
        if fused_rounds > 0:
            s = _fused_relax_rounds(layout, fused, s, fused_rounds)
        else:
            s = _relax_round(backend, layout, s, caps=caps)
        return s._replace(iters=s.iters + 1)

    out = jax.lax.while_loop(cond, body, init)
    return out.dist, out.parent, out.metrics


def repair_relax(layout, dist, parent, frontier, *, backend="segment_min",
                 max_iters=1_000_000, fused_rounds=0):
    """Monotone re-relaxation to fixpoint from a repaired tentative state
    (the engine hook of :mod:`repro.delta`).

    Runs synchronized full-window relaxation rounds (``lb=0``,
    ``ub=+inf``) through the selected backend until no distance improves:
    each round's frontier is exactly the vertices the previous round
    improved, so the work is proportional to the delta's blast radius,
    not the graph.  Starting from a valid upper-bound state whose
    frontier covers every vertex that can initiate an improvement
    (:func:`repro.delta.repair` constructs one from an
    :class:`~repro.delta.AppliedDelta`), the fixpoint dist/parent are
    bitwise-identical to a from-scratch solve on the patched graph —
    the relaxation primitives (windowed candidates, parent-edge
    exclusion, leaf pruning, deterministic min/min-src tie-break) are
    the very same ones the stepping engines run, and the rounded
    fixpoint is schedule-independent.

    Metrics start from zero and count only the repair's own work
    (``n_relax``/``n_rounds``/... of the re-relaxation), which is what
    the delta benchmarks compare against a full recompute.  Returns
    ``(dist, parent, metrics)``.
    """
    be = relax.get_backend(backend)
    if fused_rounds > 0 and not isinstance(layout, relax.BlockedGraph):
        raise ConfigError(
            "fused_rounds needs a blocked layout for repair; got "
            f"{type(layout).__name__}")
    n = dist.shape[0]
    dist = jnp.asarray(dist, jnp.float32)
    parent = jnp.asarray(parent, jnp.int32)
    frontier = jnp.asarray(frontier, bool)
    if parent.shape != (n,) or frontier.shape != (n,):
        raise ValueError("dist/parent/frontier shapes disagree")
    with profiling.annotate("repro:repair_dispatch"):
        return _repair_jit(layout, dist, parent, frontier, be, max_iters,
                           fused_rounds, _compact_caps(be, layout))


def prepare_layout(g: DeviceGraph, backend="segment_min", **backend_opts):
    """Build a backend's graph layout once (host-side, outside ``jit``)."""
    be = relax.get_backend(backend)
    with profiling.annotate(f"repro:prepare_layout:{be.name}"):
        return be.prepare(g, **backend_opts)


def _engine_args(g: DeviceGraph, config, backend, max_iters, alpha, beta,
                 fused_rounds, policy, backend_opts):
    """Resolve the engine knobs from either an
    :class:`~repro.core.config.EngineConfig` or the loose engine-level
    kwargs — never both (:meth:`EngineConfig.from_loose` is the shared
    gate, so loose kwargs go through exactly the config validation)."""
    config = EngineConfig.from_loose(
        config, "engine", backend=backend, max_iters=max_iters, alpha=alpha,
        beta=beta, fused_rounds=fused_rounds, policy=policy, **backend_opts)
    r = as_resolved(config, n=g.n, m=g.m).require("single")
    return (relax.get_backend(r.backend), r.max_iters, r.alpha, r.beta,
            r.fused_rounds, r.trace_cap, r.policy, r.layout_opts(), r)


def _resolve_alt(g: DeviceGraph, landmarks, r, goal: str):
    """The traced :class:`~repro.core.relax.AltData` bundle for this
    solve, or None.  An explicit ``landmarks`` (a
    :class:`~repro.core.landmarks.LandmarkSet` or a raw ``AltData``)
    wins; otherwise a resolved ``use_alt=True`` config builds a set on
    the fly — uncached, so prefer the facade/registry, which cache per
    graph.  ALT bounds need a target: only p2p goals use them."""
    if goal != "p2p":
        return None
    if landmarks is None and getattr(r, "use_alt", False):
        from .landmarks import build_landmarks
        landmarks = build_landmarks(g, n_landmarks=r.n_landmarks,
                                    strategy=r.landmark_strategy)
    if landmarks is None:
        return None
    return getattr(landmarks, "alt_data", landmarks)


def sssp(g: DeviceGraph, source, *, backend=None, layout=None,
         max_iters=None, alpha=None, beta=None, fused_rounds=None,
         policy=None, goal: str = "tree", goal_param=None, config=None,
         landmarks=None, **backend_opts):
    """Run the heuristic SSSP algorithm from ``source``.

    This is the single-device *engine* entry point; prefer the
    :class:`repro.api.Solver` facade, which owns layout building and
    tier resolution.  ``config`` accepts an
    :class:`~repro.core.config.EngineConfig` (or a resolved one) in
    place of the loose ``backend``/``alpha``/``beta``/``max_iters``
    kwargs; pass a prebuilt ``layout`` (from :func:`prepare_layout`) to
    amortize backend preprocessing across calls.  ``goal``/``goal_param``
    select an early-exit query variant (see :data:`GOALS`).  Returns
    ``(dist, parent, metrics)`` — or ``(dist, parent, metrics,
    trace_buf)`` when the config enables per-round tracing
    (``EngineConfig(trace=True)``; materialize the device ring with
    :func:`repro.obs.materialize_trace`).  ``landmarks`` (a
    :class:`~repro.core.landmarks.LandmarkSet`) enables exact ALT
    goal-directed pruning for p2p goals; with ``use_alt=True`` in the
    config and no explicit set, one is built on the fly.
    """
    args, tc = _sssp_args(
        g, source, backend=backend, layout=layout, max_iters=max_iters,
        alpha=alpha, beta=beta, fused_rounds=fused_rounds, policy=policy,
        goal=goal, goal_param=goal_param, config=config,
        landmarks=landmarks, **backend_opts)
    with profiling.annotate("repro:sssp_dispatch"):
        out = _sssp_jit(*args)
    return out if tc > 0 else out[:3]


def _sssp_args(g: DeviceGraph, source, *, backend=None, layout=None,
               max_iters=None, alpha=None, beta=None, fused_rounds=None,
               policy=None, goal: str = "tree", goal_param=None,
               config=None, landmarks=None, **backend_opts):
    """``(args, trace_capacity)``: what :func:`sssp` passes ``_sssp_jit``."""
    be, max_iters, alpha, beta, fr, tc, pol, opts, r = _engine_args(
        g, config, backend, max_iters, alpha, beta, fused_rounds, policy,
        backend_opts)
    if layout is None:
        layout = be.prepare(g, **opts)
    gp = goal_param_array(goal, goal_param)
    _check_goal_bounds(goal, gp, g.n)
    alt_data = _resolve_alt(g, landmarks, r, goal)
    return (g, layout, jnp.int32(source), be, max_iters, alpha, beta, goal,
            gp, fr, tc, pol, alt_data, r.p2p_mode), tc


def _shim(name: str, replacement: str) -> None:
    warnings.warn(
        f"{name} is deprecated: open a solver session instead — "
        f"`repro.api.Solver.open(g).solve({replacement})` (one facade "
        f"for every goal kind, tier, and backend)",
        FacadeDeprecationWarning, stacklevel=3)


def sssp_p2p(g: DeviceGraph, source, target, **kw):
    """Deprecated shim over the p2p goal (see :mod:`repro.api`).

    ``dist[target]`` and the parent chain target -> source are bitwise
    equal to the full-tree result; other entries may be tentative."""
    _shim("sssp_p2p", "SolveSpec.p2p(source, target)")
    return sssp(g, source, goal="p2p", goal_param=target, **kw)


def sssp_bounded(g: DeviceGraph, source, bound, **kw):
    """Deprecated shim over the distance-bounded goal (see
    :mod:`repro.api`): early exit once every vertex with
    ``dist <= bound`` is settled (entries above ``bound`` are tentative)."""
    _shim("sssp_bounded", "SolveSpec.bounded(source, bound)")
    return sssp(g, source, goal="bounded", goal_param=bound, **kw)


def sssp_knear(g: DeviceGraph, source, k, **kw):
    """Deprecated shim over the k-nearest goal (see :mod:`repro.api`):
    early exit once the source plus its ``k`` nearest vertices are
    settled (their distances are final; the rest tentative)."""
    _shim("sssp_knear", "SolveSpec.knear(source, k)")
    return sssp(g, source, goal="knear", goal_param=k, **kw)


def sssp_batch(g: DeviceGraph, sources, *, backend=None,
               layout=None, max_iters=None, alpha=None, beta=None,
               fused_rounds=None, policy=None, goal: str = "tree",
               goal_params=None, config=None, landmarks=None,
               **backend_opts):
    """Batched multi-source SSSP: one fused computation over ``sources``.

    The per-source state (dist/parent/frontier/window) is stacked along a
    leading batch axis via ``vmap``; sources that terminate early are
    masked out by the batched ``while_loop`` while the rest keep stepping.
    All slots share the (static) ``goal`` kind but carry per-slot
    ``goal_params`` (targets / bounds / k values).  ``config`` replaces
    the loose engine kwargs exactly as in :func:`sssp`.  Returns
    ``(dist, parent, metrics)`` with a leading ``[S]`` axis (plus a
    batch-stacked trace ring when the config enables tracing, as in
    :func:`sssp`).
    """
    args, tc = _sssp_batch_args(
        g, sources, backend=backend, layout=layout, max_iters=max_iters,
        alpha=alpha, beta=beta, fused_rounds=fused_rounds, policy=policy,
        goal=goal, goal_params=goal_params, config=config,
        landmarks=landmarks, **backend_opts)
    with profiling.annotate("repro:sssp_batch_dispatch"):
        out = _sssp_batch_jit(*args)
    return out if tc > 0 else out[:3]


def _sssp_batch_args(g: DeviceGraph, sources, *, backend=None, layout=None,
                     max_iters=None, alpha=None, beta=None,
                     fused_rounds=None, policy=None, goal: str = "tree",
                     goal_params=None, config=None, landmarks=None,
                     **backend_opts):
    """``(args, trace_capacity)``: what :func:`sssp_batch` passes
    ``_sssp_batch_jit``."""
    be, max_iters, alpha, beta, fr, tc, pol, opts, r = _engine_args(
        g, config, backend, max_iters, alpha, beta, fused_rounds, policy,
        backend_opts)
    if layout is None:
        layout = be.prepare(g, **opts)
    sources = jnp.asarray(sources, jnp.int32)
    if goal == "tree" and goal_params is None:
        goal_params = [0] * sources.shape[0]
    gp = goal_param_array(goal, goal_params)
    if gp.shape != sources.shape:
        raise ValueError(f"goal_params shape {gp.shape} != sources shape "
                         f"{sources.shape}")
    _check_goal_bounds(goal, gp, g.n)
    alt_data = _resolve_alt(g, landmarks, r, goal)
    return (g, layout, sources, be, max_iters, alpha, beta, goal, gp, fr,
            tc, pol, alt_data, r.p2p_mode), tc


def compiled_text(g: DeviceGraph, sources, *, batched: bool = False,
                  **kw) -> str:
    """The optimized HLO text of the program that :func:`sssp` (or,
    ``batched``, :func:`sssp_batch`) runs with these arguments; it is
    compiled, or found in the compile cache, and not run."""
    fn, make = ((_sssp_batch_jit, _sssp_batch_args) if batched
                else (_sssp_jit, _sssp_args))
    args, _ = make(g, sources, **kw)
    return fn.lower(*args).compile().as_text()


def metrics_dict(metrics: SsspMetrics) -> dict:
    """Every ``SsspMetrics`` field as a host-side scalar, one key per
    field: logical counters (:data:`LOGICAL_METRIC_FIELDS`) as ``int``,
    physical counters (:data:`PHYSICAL_METRIC_FIELDS`) as ``float``.

    This is the canonical machine-readable export shape — the benchmark
    JSON emitter and the facade's telemetry both use it, and the export
    invariants (every field present, every value finite) are pinned by
    tests."""
    out = {}
    for name in SsspMetrics._fields:
        v = np.asarray(getattr(metrics, name))
        out[name] = float(v) if name in PHYSICAL_METRIC_FIELDS else int(v)
    return out


def normalized_metrics(g_deg, dist, metrics: SsspMetrics) -> dict:
    """Paper §4 normalizations: nFrontier, nSync, nTrav (host-side)."""
    import numpy as np
    deg = np.asarray(g_deg)
    d = np.asarray(dist)
    reach = np.isfinite(d)
    n_reach = max(int(reach.sum()), 1)
    nonleaf = max(int((reach & (deg > 1)).sum()), 1)
    logn = max(np.log2(max(deg.shape[0], 2)), 1.0)
    return {
        "nFrontier": float(metrics.n_extended) / nonleaf,
        "nSync": float(metrics.n_rounds) / logn,
        "nTrav": (float(metrics.n_trav) + float(metrics.n_pull_trav)) / n_reach,
        "nTrav_push": float(metrics.n_trav) / n_reach,
        "nTrav_pull": float(metrics.n_pull_trav) / n_reach,
        "n_steps": int(metrics.n_steps),
        "n_rounds": int(metrics.n_rounds),
        "n_relax": int(metrics.n_relax),
        "n_updates": int(metrics.n_updates),
        "n_pruned": int(metrics.n_pruned),
        "n_tiles_scanned": int(metrics.n_tiles_scanned),
        "n_tiles_dense": int(metrics.n_tiles_dense),
        "n_invocations": int(metrics.n_invocations),
        "n_compact_rounds": int(metrics.n_compact_rounds),
        "reachable": n_reach,
    }
