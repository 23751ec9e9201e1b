"""JAX backend for the unified workflow simulator (``backend="jax"``).

One compiled program sweeps (seeds x placements x requests): every ``Dist``
draw is pre-sampled as a device array, the node-major
poke/payload/prepare/start/end recurrence runs as ``jax.lax.scan`` over the
topo order under ``jit``, and the whole thing is ``vmap``-ed twice — over
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

Three structural observations make the compiled program fast on a single
core (and they are exactly the levers the numpy path pulls, batched):

- the poke cascade is draw-free and uniform over requests — ``poke[v]``
  is ``t0 + depth(v) * msg_latency`` where ``depth`` is a static
  shortest-hop count through poke-enabled nodes, so it is precomputed on
  the host per placement instead of carried through the scan;
- the lognormal factor ``exp(sigma * z)`` only depends on sigma, and a
  placement set reuses a handful of sigmas, so factors are tabulated per
  (seed, distinct sigma) and gathered per placement — sampling cost is
  per SEED, not per (seed x placement);
- the cold-start recurrence (the one sequential piece) is the
  ``kernels/cold_scan.py`` Pallas kernel on TPU and its log-depth
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
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cold_scan import cold_scan_parallel
from repro.kernels.ops import cold_scan as cold_scan_kernel
from repro.obs.trace import span


class _Graph(NamedTuple):
    """Structure shared by every placement: topology + drift scale arrays."""

    pred_idx: jax.Array  # (V, maxP) int32 rows into topo order (0-padded)
    pred_mask: jax.Array  # (V, maxP) bool — which slots are real edges
    is_source: jax.Array  # (V,) bool
    is_sink: jax.Array  # (V,) bool
    compute_scale: jax.Array  # (n_platforms, n) drift masks (ones w/o drift)
    transfer_scale: jax.Array  # (n_platforms, n)
    fetch_scale: jax.Array  # (n_platforms, n)


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
    transfer: jax.Array  # (V, maxP) per-edge payload FIRST-byte transfer
    #   (== the whole-object transfer when streaming is off)
    transfer_last: jax.Array  # (V, maxP) per-edge LAST-byte transfer
    #   (only read by the recurrence when use_stream; == transfer otherwise)
    plat_idx: jax.Array  # (V,) int32 rows into the drift scale arrays
    fault_extra: jax.Array  # (V, n) per-(node, request) retry-backoff
    #   seconds from the host-precomputed fault plane ((V, 1) zeros and
    #   never read when use_faults is off — the hash-based plane needs no
    #   device rng, so it rides the scan like the drift masks do)


def use_pallas() -> bool:
    """Whether the sweep runs the Pallas cold-scan kernel: on a TPU."""
    return jax.default_backend() == "tpu"


def _cold_mask(t0s, warm_end, cold_end, keep_warm, use_pallas):
    # under _sweep's two vmaps each (1, n) row joins one kernel call's lanes
    if use_pallas:
        return cold_scan_kernel(t0s, warm_end[None, :], cold_end[None, :], keep_warm)[0]
    return cold_scan_parallel(t0s, warm_end, cold_end, keep_warm)


def _simulate_one(
    placed, factors, graph, t0s, msg, inv_chunks, prefetch, use_drift,
    use_pallas, use_stream, use_faults, sample_idx=None,
):
    """One (seed, placement) request stream: the node-major recurrence of
    ``_run_graph_vectorized`` as a scan over topo order. ``factors`` are
    the seed's three lognormal tables ``exp(sigma_u * z)``, each (U, V, n).
    Returns the (n,) per-request totals — plus, when ``sample_idx`` (a
    (k,) request-index array) is given, the per-node scan ys at those
    columns (payload, effective cold, fetch, compute, end; each (V, k)) so
    the host can rebuild ``obs`` traces for the sampled requests. The
    gather rides the existing scan outputs: the totals arithmetic is
    untouched, and no extra randomness is drawn."""
    f_cold, f_fetch, f_compute = factors
    V, n = f_cold.shape[1:]
    dtype = t0s.dtype
    rows = jnp.arange(V)

    def draws(table, sig_idx, median):
        # select each node's factor row by its sigma index. The table's U
        # axis is static and tiny (distinct sigmas across the placement
        # set), so an unrolled where-chain beats a general gather — under
        # the double vmap a gather lowers to per-element loads on CPU.
        factor = table[0]
        for u in range(1, table.shape[0]):
            factor = jnp.where((sig_idx == u)[:, None], table[u], factor)
        return median[:, None] * factor  # (V, n)

    cold = draws(f_cold, placed.cold_sig, placed.cold_median)
    fetch = draws(f_fetch, placed.fetch_sig, placed.fetch_median)
    compute = draws(f_compute, placed.compute_sig, placed.compute_median)
    transfer = placed.transfer[:, :, None]  # (V, maxP, 1)
    transfer_last = placed.transfer_last[:, :, None] if use_stream else None
    if use_drift:
        # drift rescales AFTER sampling (the draw-neutral contract); a
        # degraded platform slows every link it terminates (max endpoint)
        compute = compute * graph.compute_scale[placed.plat_idx]
        fetch = fetch * graph.fetch_scale[placed.plat_idx]
        tr_dst = graph.transfer_scale[placed.plat_idx]  # (V, n)
        tr_src = graph.transfer_scale[placed.plat_idx[graph.pred_idx]]
        tr_sc = jnp.maximum(tr_src, tr_dst[:, None, :])
        transfer = transfer * tr_sc
        if use_stream:
            transfer_last = transfer_last * tr_sc

    inf = jnp.array(jnp.inf, dtype)
    xs = (
        rows,
        graph.pred_idx,
        graph.pred_mask,
        graph.is_source,
        graph.is_sink,
        placed.poke_depth,
        placed.keep_warm,
        cold,
        fetch,
        compute,
        jnp.broadcast_to(transfer, (V,) + transfer.shape[1:]),
    )
    if use_stream:
        xs = xs + (
            jnp.broadcast_to(transfer_last, (V,) + transfer_last.shape[1:]),
        )
    if use_faults:
        xs = xs + (placed.fault_extra,)

    def body(end_all, x):
        # use_stream / use_faults are static: the traced program is
        # literally unchanged when they are False (no extra scan inputs,
        # no extra ops) — unpacked in reverse append order
        if use_faults:
            *x, fault_extra_v = x
        if use_stream:
            *x, tr_last_v = x
        (
            v,
            pidx,
            pmask,
            is_src,
            is_sink,
            depth,
            kw,
            cold_v,
            fetch_v,
            compute_v,
            tr_v,
        ) = x
        # payload join (max over in-edges of upstream end + transfer);
        # with streaming the join gates on FIRST bytes and the last bytes
        # bound the compute tail below
        arrivals = jnp.where(pmask[:, None], end_all[pidx] + tr_v, -inf)
        payload = jnp.where(is_src, t0s + msg / 2, jnp.max(arrivals, axis=0))
        if use_stream:
            arrivals_last = jnp.where(
                pmask[:, None], end_all[pidx] + tr_last_v, -inf
            )
            payload_last = jnp.where(
                is_src, t0s + msg / 2, jnp.max(arrivals_last, axis=0)
            )
        # start/end under both cold hypotheses, then the cold scan
        if prefetch:
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
        if use_stream:
            # per-chunk pipeline tail (closed form, matching the numpy
            # path); sources have no in-edges, so their tail never binds
            tail = jnp.where(is_src, -inf, payload_last + compute_v * inv_chunks)
            warm_end = jnp.maximum(warm_end, tail)
            cold_end = jnp.maximum(cold_end, tail)
        if use_faults:
            # retry backoffs delay the node under both hypotheses, after
            # the streaming tail and before the cold scan — the exact
            # ordering of the scalar and numpy paths. Exhausted budgets
            # are applied HOST-side to the totals (inf would poison the
            # cold recurrence), so the compiled sweep stays finite.
            warm_end = warm_end + fault_extra_v
            cold_end = cold_end + fault_extra_v
        mask = _cold_mask(t0s, warm_end, cold_end, kw, use_pallas)
        end_v = jnp.where(mask, cold_end, warm_end)
        sink_row = jnp.where(is_sink, end_v, -inf)
        if sample_idx is not None:
            cold_eff = jnp.where(mask, cold_v, jnp.zeros_like(cold_v))
            sampled = (
                payload[sample_idx],
                cold_eff[sample_idx],
                fetch_v[sample_idx],
                compute_v[sample_idx],
                end_v[sample_idx],
            )
            return end_all.at[v].set(end_v), (sink_row, sampled)
        return end_all.at[v].set(end_v), sink_row

    _, ys = jax.lax.scan(body, jnp.zeros((V, n), dtype), xs)
    if sample_idx is not None:
        sink_ends, sampled = ys
        return jnp.max(sink_ends, axis=0) - t0s, sampled
    return jnp.max(ys, axis=0) - t0s


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
    ``sample_idx``, also the sampled per-node ys pytree (leaves gain the
    (seeds, placements) leading axes)."""
    V = graph.pred_idx.shape[0]
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
    retry-backoff plane (``_Placement.fault_extra``, a scan input like the
    drift masks) and a (n,) request-failed mask; the planes come from the
    same hash-based ``FaultSchedule.plane`` the scalar and numpy backends
    price, so all three agree bit-for-bit. Returns ``(placed, sigmas,
    graph, fault_failed)`` with ``fault_failed`` a (P, n) bool array (all
    False when no schedule is active)."""
    f64 = dtype
    V = len(order)
    n = len(t0s)
    max_p = max([1] + [len(preds[v]) for v in order])
    idx_of = {v: i for i, v in enumerate(order)}
    pred_idx = np.zeros((V, max_p), np.int32)
    pred_mask = np.zeros((V, max_p), bool)
    for i, v in enumerate(order):
        for j, u in enumerate(preds[v]):
            pred_idx[i, j] = idx_of[u]
            pred_mask[i, j] = True
    is_source = np.array([not preds[v] for v in order])
    is_sink = np.array([not succs[v] for v in order])

    plat_names = list(sim.platforms)
    plat_row = {name: i for i, name in enumerate(plat_names)}
    scales = np.ones((3, len(plat_names), n), f64)
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
            "transfer": np.zeros((V, max_p), f64),
            "transfer_last": np.zeros((V, max_p), f64),
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
            for j, u in enumerate(preds[v]):
                # routes through the table-aware per-edge resolver, so a
                # calibrated transfer_table is honored on this backend too
                first, last = sim._pair_transfer_fl(steps[u], step)
                row["transfer"][i, j] = first
                row["transfer_last"][i, j] = last
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
        pred_idx,
        pred_mask,
        is_source,
        is_sink,
        compute_scale=scales[0],
        transfer_scale=scales[1],
        fetch_scale=scales[2],
    )
    return placed, sigmas, graph, fault_failed


def run_batched(sim, order, step_sets, preds, succs, t0s, prefetch, seeds,
                drift=None, dtype=np.float64, sample_idx=None, stream=None,
                faults=None, retry=None, build=None):
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
    another plane riding the scan next to the cold-start inputs (a static
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
    with the ``fetched_bytes`` read back.
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
    # whole-object scan — first == last there, so the tail never binds
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
            jnp.asarray(np.asarray(t0s, dtype)),
            jnp.asarray(dtype(sim.msg)),
            jnp.asarray(dtype(1.0 / stream.chunks) if use_stream else dtype(1.0)),
            jnp.asarray(np.asarray(sample_idx, np.int32))
            if sample_idx is not None
            else None,
        )
        if build is not None:
            if build.record is not None:
                build.set(host_bytes=sum(a.nbytes for a in jax.tree.leaves(args)))
            build.end()
        with span("geoff.sweep.dispatch"):
            out = _sweep(
                *args,
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
