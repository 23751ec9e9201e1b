"""JAX backend for the unified workflow simulator (``backend="jax"``).

One compiled program sweeps (seeds x placements x requests): every ``Dist``
draw is pre-sampled as a device array, the node-major
poke/payload/prepare/start/end recurrence runs level by level over the
graph's topological levels under ``jit`` (the nodes of one level are
independent rows), and the whole thing is ``vmap``-ed twice — over
candidate placements (same graph, different platforms/medians) and over
seeds. That is what lets ``PlacementScorer`` score an entire candidate set
in one jitted call and the benches sweep seeds x placements without a
Python loop.

The model is EXACTLY the numpy-vectorized path's
(``_run_graph_vectorized``), arithmetic mirrored operation for operation in
float64 (``jax.enable_x64`` is scoped to this module's calls; the ambient jax
config stays untouched), so at sigma=0 — where no randomness survives —
all backends agree to 1e-9. With spread, this backend has its own
draw-order contract: ``jax.random.PRNGKey(seed)`` splits into three
streams (cold / fetch / compute), each one ``(n_nodes, n_requests)``
standard-normal block laid out node-major in topo order. The normals —
and the lognormal factors ``exp(sigma * z)`` derived from them, one table
row per distinct sigma — are drawn ONCE per seed and shared by every
placement in the sweep (common random numbers): candidate comparisons are
driven by the placements, not sampling noise, and the per-placement
marginal cost is just the recurrence. Marginals are the same lognormals
as the numpy backends — medians/p99 agree within 1%
(tests/test_jaxsim.py, the jaxsim bench).

Four structural observations make the compiled program fast on a single
core (and they are exactly the levers the numpy path pulls, batched):

- the poke cascade is draw-free and uniform over requests — ``poke[v]``
  is ``t0 + depth(v) * msg_latency`` where ``depth`` is a static
  shortest-hop count through poke-enabled nodes, so it is precomputed on
  the host per placement instead of carried through the recurrence;
- the lognormal factor ``exp(sigma * z)`` only depends on sigma, and a
  placement set reuses a handful of sigmas, so factors are tabulated per
  (seed, distinct sigma) and gathered per placement — sampling cost is
  per SEED, not per (seed x placement);
- the payload join reads the graph's edge list, a level at a time, padded
  only to that level's own largest in-degree, so a request's reads scale
  with the edges, not with nodes x the widest in-degree;
- the cold-start recurrence (the one sequential piece) runs once per
  topological level over all of the level's nodes: the
  ``kernels/cold_scan.py`` Pallas kernel on TPU, whose lanes then hold
  every (seed, placement, node) row of the level, and its log-depth
  GF(2)-affine parallel scan everywhere else, whose ``while_loop`` gate
  exits immediately in regimes where no request's status depends on its
  predecessor — the batched analogue of the numpy scan's candidate list.

Not supported here (use the scalar / numpy backends): ``timing=``
(per-request feedback), ``telemetry=`` (the compiled program is pure), and
graphs reusing one (name, platform) pair across nodes (couples the cold
recurrence across nodes). Drift IS supported: ``DriftSchedule`` scale
arrays are precomputed per platform on the host and applied as masks after
sampling, exactly like the numpy path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cold_scan import cold_scan_parallel, kernel_lanes
from repro.kernels.ops import cold_scan as cold_scan_kernel
from repro.obs.trace import span


class _Level(NamedTuple):
    """One topological level: its nodes (rows into topo order, in listing
    order), each node's in-edges (ids into the edge list), and which of
    its nodes are sinks (positions in the level). Level 0 holds the
    sources, the only nodes without in-edges."""

    nodes: tuple
    edges: tuple  # per node, a tuple of edge ids
    sinks: tuple


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("compute_scale", "transfer_scale", "fetch_scale"),
    meta_fields=("edge_src", "edge_dst", "levels"),
)
@dataclass(frozen=True)
class _Graph:
    """Structure shared by every placement: the drift scale arrays and,
    static (part of the compiled program's key, fixed by the workflow),
    the edge list and its partition into topological levels."""

    compute_scale: jax.Array  # (n_platforms, n) drift masks ((n_platforms, 1)
    #   ones, never read, without drift)
    transfer_scale: jax.Array  # (n_platforms, n)
    fetch_scale: jax.Array  # (n_platforms, n)
    edge_src: tuple  # (E,) rows into topo order, grouped by destination
    edge_dst: tuple  # (E,)
    levels: tuple  # of _Level, longest path from a source


class _Sigmas(NamedTuple):
    """Distinct sigma values across the placement set, one list per draw
    stream; ``_Placement.*_sig`` rows index into the matching factor table."""

    cold: jax.Array  # (Uc,)
    fetch: jax.Array  # (Uf,)
    compute: jax.Array  # (Ux,)


class _Placement(NamedTuple):
    """Per-placement numerics; stacked with a leading axis and vmapped."""

    cold_median: jax.Array  # (V,)
    cold_sig: jax.Array  # (V,) int32 rows into the cold factor table
    keep_warm: jax.Array  # (V,) may be +inf
    fetch_median: jax.Array  # (V,)
    fetch_sig: jax.Array  # (V,)
    compute_median: jax.Array  # (V,)
    compute_sig: jax.Array  # (V,)
    poke_depth: jax.Array  # (V,) hops from a source via poke-enabled nodes
    #   (0.0 at sources, +inf where the cascade never reaches)
    transfer: jax.Array  # (E,) per-edge payload FIRST-byte transfer
    #   (== the whole-object transfer when streaming is off)
    transfer_last: jax.Array  # (E,) per-edge LAST-byte transfer
    #   (only read by the recurrence when use_stream; == transfer otherwise)
    plat_idx: jax.Array  # (V,) int32 rows into the drift scale arrays
    fault_extra: jax.Array  # (V, n) per-(node, request) retry-backoff
    #   seconds from the host-precomputed fault plane ((V, 1) zeros and
    #   never read when use_faults is off — the hash-based plane needs no
    #   device rng, so it rides the recurrence like the drift masks do)


def use_pallas() -> bool:
    """Whether the sweep runs the Pallas cold-scan kernel: on a TPU."""
    return jax.default_backend() == "tpu"


def _cold_mask(t0s, warm_end, cold_end, keep_warm, use_pallas):
    """The cold mask of one level's (L, n) rows, each row with its node's
    ``keep_warm`` (L,). Under _sweep's two vmaps the Pallas path's rows of
    every (seed, placement) join one kernel call's lanes."""
    keep_warm = keep_warm[:, None]
    if use_pallas:
        return cold_scan_kernel(t0s, warm_end, cold_end, keep_warm)
    return cold_scan_parallel(t0s, warm_end, cold_end, keep_warm)


def _contiguous(idx):
    return np.array_equal(idx.ravel(), np.arange(idx.flat[0], idx.flat[0] + idx.size))


def _rows(x, idx):
    """Rows ``idx`` of ``x``, shaped like ``idx``. Static ints (any shape)
    take a slice where they run in order without a gap, else a gather; a
    traced index (a scan step's) always gathers."""
    if not isinstance(idx, np.ndarray):
        return x[idx]
    if _contiguous(idx):
        lo = int(idx.flat[0])
        return x[lo:lo + idx.size].reshape(idx.shape + x.shape[1:])
    return x[idx]


def _runs(levels):
    """The levels grouped into runs of consecutive levels of one width and
    one largest in-degree; the sources (in-degree 0) join the run after
    them where the widths agree. A run of several levels steps as one
    ``lax.scan``, so a chain is one scanned body however deep, and the
    program grows with the number of runs, not with the depth."""
    runs = []
    for lv in levels:
        width, degree = len(lv.nodes), max(map(len, lv.edges))
        if runs and runs[-1][0] == width and runs[-1][1] in (degree, 0):
            runs[-1] = (width, degree, runs[-1][2] + (lv,))
        else:
            runs.append((width, degree, (lv,)))
    return [run for _, _, run in runs]


def _join_table(run):
    """The run's in-edges padded to its largest in-degree D: (R, L, D) edge
    ids and which slots are real (None when every slot is). Padding
    repeats a node's first edge, or edge 0 at a source."""
    width = max(len(e) for lv in run for e in lv.edges)
    eids = np.array(
        [[e + (e[:1] or (0,)) * (width - len(e)) for e in lv.edges] for lv in run],
        np.int32,
    ).reshape(len(run), len(run[0].nodes), width)
    degree = np.array([[len(e) for e in lv.edges] for lv in run])
    real = np.arange(width) < degree[..., None]
    return eids, (None if real.all() else real)


def _simulate_one(
    placed, factors, graph, t0s, msg, inv_chunks, prefetch, use_drift,
    use_pallas, use_stream, use_faults, sample_idx=None,
):
    """One (seed, placement) request stream: the node-major recurrence of
    ``_run_graph_vectorized``, one step per topological level, each step
    over all of the level's nodes at once. A run of levels of one shape
    (``_runs``) steps as a scan; a level alone is traced as it is.
    ``factors`` are the seed's three lognormal tables ``exp(sigma_u * z)``,
    each (U, V, n); a level takes its nodes' rows of the draws by node
    index, so the draws stay one row per node in topo order. Returns the
    (n,) per-request totals — plus, when ``sample_idx`` (a (k,)
    request-index array) is given, the per-node values at those columns
    (payload, effective cold, fetch, compute, end; each (V, k) in topo
    order) so the host can rebuild ``obs`` traces for the sampled requests.
    The gather rides the recurrence's values: the totals arithmetic is
    untouched, and no extra randomness is drawn."""
    f_cold, f_fetch, f_compute = factors
    V, n = f_cold.shape[1:]
    dtype = t0s.dtype
    plat = placed.plat_idx

    def draws(table, sig_idx, median, scale=None):
        # select each node's factor row by its sigma index. The table's U
        # axis is static and tiny (distinct sigmas across the placement
        # set), so an unrolled where-chain beats a general gather — under
        # the placement vmap a gather would batch into a slow scatter-ish op
        factor = table[0]
        for u in range(1, table.shape[0]):
            factor = jnp.where((sig_idx == u)[:, None], table[u], factor)
        draw = median[:, None] * factor
        if scale is not None:
            # drift rescales AFTER sampling (the draw-neutral contract)
            draw = draw * scale[plat]
        # a degenerate dist (median <= 0) draws 0. The select also keeps
        # each draw one rounded value where a sum below reads it: a fused
        # multiply-add would round product and sum once, and the totals
        # would move off the numpy backend's by an ulp
        return jnp.where(median[:, None] > 0, draw, 0)  # (V, n)

    cold = draws(f_cold, placed.cold_sig, placed.cold_median)
    fetch = draws(f_fetch, placed.fetch_sig, placed.fetch_median,
                  graph.fetch_scale if use_drift else None)
    compute = draws(f_compute, placed.compute_sig, placed.compute_median,
                    graph.compute_scale if use_drift else None)
    transfer = placed.transfer[:, None]  # (E, 1)
    transfer_last = placed.transfer_last[:, None] if use_stream else None
    edge_src = np.array(graph.edge_src, np.int32)
    if use_drift:
        # a degraded platform slows every link it terminates (max
        # endpoint); a free link stays free, and a rounded value, as above
        tr_sc = jnp.maximum(
            graph.transfer_scale[plat[edge_src]],
            graph.transfer_scale[plat[np.array(graph.edge_dst, np.int32)]],
        )  # (E, n)
        transfer = jnp.where(transfer > 0, transfer * tr_sc, 0)
        if use_stream:
            transfer_last = jnp.where(transfer_last > 0, transfer_last * tr_sc, 0)

    inf = jnp.array(jnp.inf, dtype)
    source_payload = t0s + msg / 2  # the request's own message

    def step(end_all, sink_end, lv, contiguous):
        """One level. ``lv`` holds the level's values and its structure:
        static numpy where the level is traced alone, traced where it is a
        step of a run's scan. use_stream / use_faults are static: the
        traced program is literally unchanged when they are False (no
        extra inputs, no extra ops)."""
        cold_v, fetch_v, compute_v = lv["cold"], lv["fetch"], lv["compute"]
        if "src" not in lv:
            # a level of sources alone
            payload = jnp.broadcast_to(source_payload, cold_v.shape)
        else:
            # payload join (max over in-edges of upstream end + transfer);
            # with streaming the join gates on FIRST bytes and the last
            # bytes bound the compute tail below
            src_end = _rows(end_all, lv["src"])  # (L, D, n)

            def join(tr):
                arrivals = src_end + tr
                if "real" in lv:
                    arrivals = jnp.where(lv["real"][:, :, None], arrivals, -inf)
                arrival = jnp.max(arrivals, axis=1)
                if "is_source" in lv:
                    arrival = jnp.where(lv["is_source"], source_payload, arrival)
                return arrival

            payload = join(lv["tr"])
            if use_stream:
                payload_last = join(lv["tr_last"])
        # start/end under both cold hypotheses, then the cold scan
        if prefetch:
            depth = lv["depth"][:, None]
            poke_v = t0s + depth * msg
            poked = jnp.isfinite(depth)
            warm_start = jnp.where(
                poked,
                jnp.maximum(payload, poke_v + fetch_v),
                payload + fetch_v,
            )
            cold_start = jnp.where(
                poked,
                jnp.maximum(payload, poke_v + cold_v + fetch_v),
                payload + fetch_v + cold_v,
            )
        else:
            warm_start = payload + fetch_v
            cold_start = warm_start + cold_v
        warm_end = warm_start + compute_v
        cold_end = cold_start + compute_v
        if use_stream and "src" in lv:
            # per-chunk pipeline tail (closed form, matching the numpy
            # path); sources have no in-edges, so their tail never binds
            tail = payload_last + compute_v * inv_chunks
            if "is_source" in lv:
                tail = jnp.where(lv["is_source"], -inf, tail)
            warm_end = jnp.maximum(warm_end, tail)
            cold_end = jnp.maximum(cold_end, tail)
        if use_faults:
            # retry backoffs delay the node under both hypotheses, after
            # the streaming tail and before the cold scan — the exact
            # ordering of the scalar and numpy paths. Exhausted budgets
            # are applied HOST-side to the totals (inf would poison the
            # cold recurrence), so the compiled sweep stays finite.
            warm_end = warm_end + lv["fault"]
            cold_end = cold_end + lv["fault"]
        mask = _cold_mask(t0s, warm_end, cold_end, lv["keep_warm"], use_pallas)
        end_v = jnp.where(mask, cold_end, warm_end)
        nodes, sinks = lv["nodes"], lv["sinks"]
        if contiguous:
            end_all = jax.lax.dynamic_update_slice_in_dim(end_all, end_v, nodes[0], 0)
        else:
            end_all = end_all.at[nodes].set(end_v)
        if isinstance(sinks, np.ndarray):
            if sinks.any():
                last = jnp.max(_rows(end_v, np.flatnonzero(sinks)), axis=0)
                sink_end = jnp.maximum(sink_end, last)
        else:
            last = jnp.max(jnp.where(sinks[:, None], end_v, -inf), axis=0)
            sink_end = jnp.maximum(sink_end, last)
        sampled = None
        if sample_idx is not None:
            cold_eff = jnp.where(mask, cold_v, jnp.zeros_like(cold_v))
            sampled = tuple(
                a[:, sample_idx]
                for a in (payload, cold_eff, fetch_v, compute_v, end_v)
            )
        return end_all, sink_end, sampled

    end_all = jnp.zeros((V, n), dtype)
    sink_end = jnp.full((n,), -inf, dtype)
    sampled = []
    for run in _runs(graph.levels):
        # the run's values, (R, L, ...): a level takes its nodes' rows
        nodes = np.array([lv.nodes for lv in run], np.int32)
        eids, real = _join_table(run)
        xs = {
            "cold": _rows(cold, nodes),
            "fetch": _rows(fetch, nodes),
            "compute": _rows(compute, nodes),
            "keep_warm": _rows(placed.keep_warm, nodes),
            "nodes": nodes,
            "sinks": np.array([np.isin(np.arange(len(lv.nodes)), lv.sinks)
                               for lv in run]),
        }
        if prefetch:
            xs["depth"] = _rows(placed.poke_depth, nodes)
        if use_faults:
            xs["fault"] = _rows(placed.fault_extra, nodes)
        if eids.shape[-1]:
            xs["src"] = edge_src[eids]
            xs["tr"] = _rows(transfer, eids)
            if use_stream:
                xs["tr_last"] = _rows(transfer_last, eids)
            if real is not None:
                xs["real"] = real
            if not run[0].edges[0]:
                xs["is_source"] = np.arange(len(run)) == 0
        contiguous = all(_contiguous(row) for row in nodes)
        if len(run) == 1:
            end_all, sink_end, ys = step(
                end_all, sink_end, {k: v[0] for k, v in xs.items()}, contiguous
            )
            ys = None if ys is None else tuple(y[None] for y in ys)
        else:
            no_sinks = None
            if not xs["sinks"].any():
                no_sinks = xs.pop("sinks")[0]

            def body(carry, x, no_sinks=no_sinks, contiguous=contiguous):
                if no_sinks is not None:
                    x["sinks"] = no_sinks
                end_all, sink_end, ys = step(*carry, x, contiguous)
                return (end_all, sink_end), ys

            (end_all, sink_end), ys = jax.lax.scan(body, (end_all, sink_end), xs)
        if ys is not None:
            sampled.append(tuple(y.reshape((-1,) + y.shape[2:]) for y in ys))
    totals = sink_end - t0s
    if sample_idx is None:
        return totals
    # levels in order, then back to topo order
    where = np.argsort(np.concatenate([lv.nodes for lv in graph.levels]))
    return totals, tuple(
        _rows(jnp.concatenate(parts), where) for parts in zip(*sampled)
    )


@partial(
    jax.jit,
    static_argnames=(
        "prefetch", "use_drift", "use_pallas", "use_stream", "use_faults",
    ),
)
def _sweep(
    keys, placed, sigmas, graph, t0s, msg, inv_chunks, sample_idx=None,
    *, prefetch, use_drift, use_pallas, use_stream, use_faults,
):
    """(seeds, placements, requests) totals in one compiled program. With
    ``sample_idx``, also the sampled per-node values pytree (leaves gain
    the (seeds, placements) leading axes)."""
    V = placed.cold_median.shape[-1]
    n = t0s.shape[0]
    f32 = jnp.float32

    def per_seed(key):
        # one normal block per stream per seed; exp(sigma_u * z) tabulated
        # per distinct sigma and shared by every placement (CRN). In f32 —
        # exact at sigma=0 (exp(0) == 1), statistically indistinguishable
        # otherwise — the recurrence itself stays in t0s' dtype.
        key_cold, key_fetch, key_compute = jax.random.split(key, 3)

        def table(k, sig_u):
            z = jax.random.normal(k, (V, n), f32)
            return jnp.exp(sig_u.astype(f32)[:, None, None] * z).astype(t0s.dtype)

        factors = (
            table(key_cold, sigmas.cold),
            table(key_fetch, sigmas.fetch),
            table(key_compute, sigmas.compute),
        )
        return jax.vmap(
            lambda p: _simulate_one(p, factors, graph, t0s, msg, inv_chunks,
                                    prefetch, use_drift, use_pallas,
                                    use_stream, use_faults, sample_idx)
        )(placed)

    return jax.vmap(per_seed)(keys)


def _poke_depths(order, steps, preds):
    """Hop count of each node's poke through poke-enabled nodes (the whole
    cascade is ``t0 + depth * msg``: draw-free and uniform over requests,
    so it folds to one static constant per node). Sources are poked at t0
    (depth 0); a node with ``prefetch=False`` — or reachable only through
    one — is never poked (+inf)."""
    depth = {}
    for v in order:
        if not preds[v]:
            depth[v] = 0.0
        elif steps[v].prefetch:
            depth[v] = min(depth[u] for u in preds[v]) + 1.0
        else:
            depth[v] = math.inf
    return np.array([depth[v] for v in order])


def graph_levels(order, preds):
    """The partition of the nodes (rows into ``order``, a topological
    order) into topological levels: a node's level is its longest path
    from a source. Each level lists its nodes in topo order. A sweep makes
    one cold-scan call per level."""
    pos = {v: i for i, v in enumerate(order)}
    depth = []
    for v in order:
        depth.append(max((depth[pos[u]] + 1 for u in preds[v]), default=0))
    return [
        tuple(i for i, d in enumerate(depth) if d == lv)
        for lv in range(max(depth, default=-1) + 1)
    ]


def kernel_counters(graph, rows):
    """The Pallas path's counters of one sweep of ``rows`` (seed,
    placement) rows: ``levels``, its kernel calls (one per topological
    level, scanned or not); ``edges``, the in-edge slots the join reads per
    request, padding included; ``kernel_lanes``, the lane width of the
    widest call; ``kernel_lanes_total``, the lanes summed over the calls."""
    lanes = [kernel_lanes(rows * len(lv.nodes)) for lv in graph.levels]
    return {
        "levels": len(lanes),
        "edges": sum(_join_table(run)[0].size for run in _runs(graph.levels)),
        "kernel_lanes": max(lanes),
        "kernel_lanes_total": sum(lanes),
    }


def _build(
    sim, order, step_sets, preds, succs, t0s, drift, dtype, stream=None,
    faults=None, retry=None,
):
    """Host-side array construction (numpy). The transfer model is
    evaluated through ``sim._transfer_s`` — or ``sim._transfer_fl`` when a
    StreamConfig is given — so subclasses that override the whole-object
    model (e.g. the scorer's cost-model simulator) feed this backend
    unchanged.

    With a ``FaultSchedule``, each placement also gets its (V, n)
    retry-backoff plane (``_Placement.fault_extra``, read like the drift
    masks) and a (n,) request-failed mask; the planes come from the
    same hash-based ``FaultSchedule.plane`` the scalar and numpy backends
    price, so all three agree bit-for-bit. Returns ``(placed, sigmas,
    graph, fault_failed)`` with ``fault_failed`` a (P, n) bool array (all
    False when no schedule is active)."""
    f64 = dtype
    V = len(order)
    n = len(t0s)
    pos = {v: i for i, v in enumerate(order)}
    # the edge list, once per graph: grouped by destination in topo order,
    # each destination's in-edges in its preds order
    edges = [(pos[u], i) for i, v in enumerate(order) for u in preds[v]]
    in_edges = [[] for _ in order]
    for e, (_, i) in enumerate(edges):
        in_edges[i].append(e)
    levels = tuple(
        _Level(
            nodes=nodes,
            edges=tuple(tuple(in_edges[i]) for i in nodes),
            sinks=tuple(j for j, i in enumerate(nodes) if not succs[order[i]]),
        )
        for nodes in graph_levels(order, preds)
    )

    plat_names = list(sim.platforms)
    plat_row = {name: i for i, name in enumerate(plat_names)}
    # without drift the program never reads the scales: a column of ones
    # stands in for the (3, platforms, n) planes
    scales = np.ones((3, len(plat_names), n if drift is not None else 1), f64)
    if drift is not None:
        ks = np.arange(n)
        for name in plat_names:
            scales[:, plat_row[name], :] = drift.scale_arrays(ks, name)

    faults_on = faults is not None and bool(faults)
    request_ks = np.arange(n)

    def placement_arrays(steps):
        row = {
            "cold_median": np.empty(V, f64),
            "cold_sigma": np.empty(V, f64),
            "keep_warm": np.empty(V, f64),
            "fetch_median": np.empty(V, f64),
            "fetch_sigma": np.empty(V, f64),
            "compute_median": np.empty(V, f64),
            "compute_sigma": np.empty(V, f64),
            "poke_depth": _poke_depths(order, steps, preds).astype(f64),
            "transfer": np.zeros(len(edges), f64),
            "transfer_last": np.zeros(len(edges), f64),
            "plat_idx": np.zeros(V, np.int32),
            "fault_extra": np.zeros((V, n if faults_on else 1), f64),
            "fault_failed": np.zeros(n, bool),
        }
        for i, v in enumerate(order):
            step = steps[v]
            plat = sim.platforms[step.platform]
            if faults_on:
                fp = faults.plane(
                    step.name, step.platform, request_ks, retry,
                    region=plat.region,
                )
                row["fault_extra"][i] = fp.extra_s
                row["fault_failed"] |= fp.failed
            row["cold_median"][i] = plat.cold_start.median
            row["cold_sigma"][i] = plat.cold_start.sigma
            row["keep_warm"][i] = plat.keep_warm_s
            row["fetch_median"][i] = step.fetch.median
            row["fetch_sigma"][i] = step.fetch.sigma
            row["compute_median"][i] = step.compute.median
            row["compute_sigma"][i] = step.compute.sigma
            row["plat_idx"][i] = plat_row[step.platform]
        for e, (u, i) in enumerate(edges):
            # routes through the table-aware per-edge resolver, so a
            # calibrated transfer_table is honored on this backend too
            first, last = sim._pair_transfer_fl(steps[order[u]], steps[order[i]])
            row["transfer"][e] = first
            row["transfer_last"][e] = last
        return row

    # _transfer_fl reads sim.stream; pin it to THIS call's config for the
    # duration of the host-side build (spec-level overrides), then restore
    saved_stream = sim.stream
    sim.stream = stream
    try:
        all_rows = [placement_arrays(steps) for steps in step_sets]
    finally:
        sim.stream = saved_stream

    def dedup_sigmas(name):
        """Distinct sigma values across ALL placements for one stream +
        per-placement (V,) index rows into them. A degenerate dist
        (median <= 0) contributes nothing to the draw, so its sigma is
        remapped to the first entry rather than widening the table."""
        stack = np.stack([r[name + "_sigma"] for r in all_rows])
        med = np.stack([r[name + "_median"] for r in all_rows])
        stack = np.where(med > 0, stack, stack.flat[0])
        uniq, inv = np.unique(stack, return_inverse=True)
        return uniq, inv.reshape(stack.shape).astype(np.int32)

    cold_u, cold_i = dedup_sigmas("cold")
    fetch_u, fetch_i = dedup_sigmas("fetch")
    comp_u, comp_i = dedup_sigmas("compute")
    # leaves stay host-side numpy: the jitted _sweep transfers them in one
    # batched device_put instead of thirty individual dispatches
    sigmas = _Sigmas(cold_u, fetch_u, comp_u)
    placed = _Placement(
        cold_median=np.stack([r["cold_median"] for r in all_rows]),
        cold_sig=cold_i,
        keep_warm=np.stack([r["keep_warm"] for r in all_rows]),
        fetch_median=np.stack([r["fetch_median"] for r in all_rows]),
        fetch_sig=fetch_i,
        compute_median=np.stack([r["compute_median"] for r in all_rows]),
        compute_sig=comp_i,
        poke_depth=np.stack([r["poke_depth"] for r in all_rows]),
        transfer=np.stack([r["transfer"] for r in all_rows]),
        transfer_last=np.stack([r["transfer_last"] for r in all_rows]),
        plat_idx=np.stack([r["plat_idx"] for r in all_rows]),
        fault_extra=np.stack([r["fault_extra"] for r in all_rows]),
    )
    fault_failed = np.stack([r["fault_failed"] for r in all_rows])
    graph = _Graph(
        compute_scale=scales[0],
        transfer_scale=scales[1],
        fetch_scale=scales[2],
        edge_src=tuple(u for u, _ in edges),
        edge_dst=tuple(i for _, i in edges),
        levels=levels,
    )
    return placed, sigmas, graph, fault_failed


def _resident(sim, args):
    """``args`` for the sweep, with the leaves a simulator sweeps again on
    the device. ``sim`` keeps its last sweep's inputs: a leaf whose bytes
    equal the last one's (in a tree of the same structure) is sent to the
    device once and then reused, so a simulator that sweeps one workflow
    again under new seeds sends only the seeds' keys. A leaf seen for the
    first time goes to the sweep as it is, in the jit's one batched
    transfer, as before."""
    leaves, tree = jax.tree.flatten(args)
    last = getattr(sim, "_jax_resident", None)
    if last is None or last[0] != tree:
        last = (tree, [None] * len(leaves), [None] * len(leaves))
    seen = [b is not None and _same_bytes(a, b) for a, b in zip(leaves, last[1])]
    dev = [d if s else None for s, d in zip(seen, last[2])]
    send = [i for i, (s, d) in enumerate(zip(seen, dev)) if s and d is None]
    if send:
        for i, d in zip(send, jax.device_put([leaves[i] for i in send])):
            dev[i] = d
    sim._jax_resident = (tree, leaves, dev)
    return jax.tree.unflatten(
        tree, [a if d is None else d for a, d in zip(leaves, dev)]
    )


def _same_bytes(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    word = f"u{a.dtype.itemsize}"  # bits, not values: -0.0 is not 0.0
    return np.array_equal(a.reshape(-1).view(word), b.reshape(-1).view(word))


def run_batched(sim, order, step_sets, preds, succs, t0s, prefetch, seeds,
                drift=None, dtype=np.float64, sample_idx=None, stream=None,
                faults=None, retry=None, build=None, sweep=None):
    """The jax backend's one entry point: simulate every (seed, placement)
    pair of one workflow graph in a single compiled call.

    ``sim`` is the host ``WorkflowSimulator`` (platforms, msg latency,
    transfer model); ``step_sets`` is a list of ``{node_id: SimStep}``
    placements sharing (order, preds, succs); ``seeds`` the integer seed
    axis; ``drift`` overrides ``sim.drift`` when given. Returns a
    ``(len(seeds), len(step_sets), len(t0s))`` ``dtype`` numpy array of
    per-request totals.

    ``dtype``: float64 (default) reproduces the numpy backend bit-for-bit
    at sigma=0 (the equivalence gates run on it); float32 halves the
    memory traffic of the compiled sweep — the recurrence is
    memory-bound — and is statistically indistinguishable (the medians
    the scorer and benches consume move by ~1e-7 relative), so bulk
    candidate scoring uses it.

    ``sample_idx``: optional (k,) request indices. When given, the return
    value becomes ``(totals, sampled)`` where ``sampled`` is a 5-tuple of
    ``(seeds, placements, V, k)`` numpy arrays (payload, effective cold,
    fetch, compute, end at the sampled requests) for host-side ``obs``
    trace reconstruction. The totals are computed by the identical
    arithmetic either way.

    ``stream``: optional ``StreamConfig``. Splits every edge into a
    (first_byte, last_byte) transfer pair host-side and — when chunks > 1
    — adds the per-chunk pipeline tail to the recurrence (a static branch:
    with ``stream=None`` the compiled program is unchanged). ``chunks=1``
    keeps the whole-object recurrence, so totals stay bit-for-bit.

    ``faults`` / ``retry``: optional ``FaultSchedule`` / ``RetryPolicy``.
    The hash-based fault plane is precomputed host-side per placement —
    another plane read next to the cold-start inputs (a static
    ``use_faults`` branch, program unchanged when off) — and exhausted
    retry budgets turn the affected requests' totals into ``inf`` after
    the sweep (the compiled recurrence itself stays finite). The fault
    outcomes are shared with the scalar/numpy backends bit-for-bit, and
    are identical across every placement's SHARED (step, platform) cells
    (a moved step gets the moved cell's plane — what lets the scorer judge
    failover candidates under live outages).

    ``build``: the caller's open ``geoff.sweep.build`` program span
    (``repro.obs.span``; ``simulate_placements`` opens it at its entry),
    which ends here where the sweep is dispatched, with the ``host_bytes``
    handed to the sweep as its counter. The phases after it are the
    ``geoff.sweep.dispatch``, ``.wait`` and ``.fetch`` spans, the last
    with the ``fetched_bytes`` read back. ``sweep``: the caller's open
    ``geoff.sweep`` span, which gets ``kernel_counters`` on the Pallas path.

    ``sim`` keeps the inputs it sweeps again on the device (``_resident``):
    a capacity planner's replays of one workflow under new seeds send only
    the seeds' keys.
    """
    if drift is None:
        drift = sim.drift
    if sim.timing is not None:
        raise ValueError(
            "backend='jax' does not support timing=: the poke controller "
            "learns from per-request feedback; use backend='scalar'"
        )
    for steps in step_sets:
        keys = [(steps[v].name, steps[v].platform) for v in order]
        if len(set(keys)) != len(keys):
            raise ValueError(
                "backend='jax' needs a unique (name, platform) per node — "
                "a duplicated pair couples the cold-start recurrence "
                "across nodes; use backend='scalar'"
            )
    seeds = [int(s) for s in seeds]
    n = len(t0s)
    if n == 0 or not step_sets or not seeds:
        empty = np.empty((len(seeds), len(step_sets), n))
        if sample_idx is not None:
            V = len(order)
            z = np.empty((len(seeds), len(step_sets), V, 0))
            return empty, (z, z, z, z, z)
        return empty
    dtype = np.dtype(dtype).type
    # the recurrence only changes when first != last bytes is possible;
    # chunks=1 (even with P2P rerouting the transfer VALUES) keeps the
    # whole-object recurrence — first == last there, so the tail never binds
    use_stream = stream is not None and stream.chunks > 1
    use_faults = faults is not None and bool(faults)
    with jax.enable_x64(True):
        placed, sigmas, graph, fault_failed = _build(
            sim, order, step_sets, preds, succs, t0s, drift, dtype,
            stream=stream, faults=faults, retry=retry,
        )
        # raw threefry key layout ([hi, lo] uint32 words of the seed) —
        # identical to stacking jax.random.PRNGKey(s), minus S dispatches
        sarr = np.asarray([s & 0xFFFFFFFFFFFFFFFF for s in seeds], np.uint64)
        keys = np.stack(
            [sarr >> np.uint64(32), sarr & np.uint64(0xFFFFFFFF)], axis=-1
        ).astype(np.uint32)
        args = (
            keys,
            placed,
            sigmas,
            graph,
            np.array(t0s, dtype),
            dtype(sim.msg),
            dtype(1.0 / stream.chunks) if use_stream else dtype(1.0),
            np.array(sample_idx, np.int32) if sample_idx is not None else None,
        )
        if sweep is not None and sweep.record is not None and use_pallas():
            sweep.set(**kernel_counters(graph, len(seeds) * len(step_sets)))
        if build is not None:
            if build.record is not None:
                build.set(host_bytes=sum(a.nbytes for a in jax.tree.leaves(args)))
            build.end()
        with span("geoff.sweep.dispatch"):
            out = _sweep(
                keys,
                *_resident(sim, args[1:]),
                prefetch=bool(prefetch),
                use_drift=drift is not None,
                use_pallas=use_pallas(),
                use_stream=use_stream,
                use_faults=use_faults,
            )
        with span("geoff.sweep.wait"):
            # queue the copies to the host behind the sweep, where reading
            # the pending result would queue them, before waiting for it
            for a in jax.tree.leaves(out):
                a.copy_to_host_async()
            jax.block_until_ready(out)

        def mark_failed(totals):
            # dead requests are priced as-if-completed inside the sweep
            # (the cold recurrence must stay finite and backend-identical)
            # but reported as never finishing — same post-step the numpy
            # backend applies
            if use_faults and fault_failed.any():
                return np.where(fault_failed[None, :, :], np.inf, totals)
            return totals

        with span("geoff.sweep.fetch") as fetch:
            fetched = [np.asarray(a) for a in jax.tree.leaves(out)]
            fetch.set(fetched_bytes=sum(a.nbytes for a in fetched))
            totals = mark_failed(fetched[0])
        if sample_idx is not None:
            return totals, tuple(fetched[1:])
        return totals
