"""Plain reference of GeoFF's workflow simulation over a workflow DAG.

A straightforward implementation of the semantics the simulator documents,
written from them and importing nothing of the program. The workflow is a
configuration's ``workflow`` steps, listed in a topological order, and its
``edges`` (``[[src, dst], ...]`` by step name); a configuration without
``edges`` is the chain in ``workflow`` order. Per request:

- request k of a stream arrives at ``t0[k] = k * interarrival``; a source's
  payload lands at ``t0 + msg / 2``, any other step's at the latest over
  its in-edges of the predecessor's end plus that edge's transfer;
- the poke cascade reaches step v after ``depth(v)`` messages: depth 0 at a
  source, elsewhere the least depth of its predecessors plus one, and
  infinite where pre-fetching is off;
- a poked step prepares from its poke: a warm instance is ready at
  ``poke + fetch``, a cold one at ``poke + cold + fetch``, and the step
  starts at the later of its payload and its preparation; a step never
  poked starts at ``payload + cold + fetch`` (cold only where cold); it
  ends ``compute`` after its start;
- every step is its own instance: it is cold when the request arrives more
  than ``keep_warm_s`` after the previous request's end on that step, and
  the first request always finds it cold unless ``keep_warm_s`` is
  infinite;
- a request's total is the latest end over the sinks minus its arrival.

Draws follow the simulator's common-random-numbers contract: the seed
``s`` is the raw threefry key ``[s >> 32, s & 0xffffffff]``, split into
three streams (cold, fetch, compute), each a (steps, requests) block of
float32 standard normals, one row per step in listing order; a draw is
``median * exp(sigma * z)``, and a median of 0 draws 0. The normals are
drawn here with ``jax.random`` from the seed; everything after them is
numpy in the dtype given (float64 for the reference, bfloat16 for its
control).
"""

from __future__ import annotations

import functools
import math

import numpy as np


def edges(cfg: dict) -> list:
    """The workflow's edges as (src, dst) step names: the configuration's
    ``edges``, else the chain in ``workflow`` order."""
    steps = [s["name"] for s in cfg["workflow"]]
    if "edges" in cfg:
        return [tuple(e) for e in cfg["edges"]]
    return list(zip(steps, steps[1:]))


def in_edges(cfg: dict) -> list:
    """Per step, in listing order, the positions of its predecessors. The
    edges must name steps and point forward in the listing."""
    pos = {s["name"]: i for i, s in enumerate(cfg["workflow"])}
    ins = [[] for _ in pos]
    for a, b in edges(cfg):
        if a not in pos or b not in pos:
            raise ValueError(f"edge {a!r} -> {b!r} names no step of the workflow")
        if pos[a] >= pos[b]:
            raise ValueError(f"edge {a!r} -> {b!r}: steps are not in topological order")
        ins[pos[b]].append(pos[a])
    return ins


def normals(seed: int, steps: int, n: int) -> list:
    """The three (steps, n) float32 normal blocks of one seed."""
    import jax
    import jax.numpy as jnp

    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    key = np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)
    return [
        np.asarray(jax.random.normal(k, (steps, n), jnp.float32))
        for k in jax.random.split(key, 3)
    ]


def cold_mask(t0, warm_end, cold_end, keep_warm):
    """Which requests find their instance cold, by the recurrence above.
    Where the gap clears ``keep_warm`` under both hypotheses for the
    previous end, or under neither, the answer is the same either way; in
    between it is the opposite of the previous request's, so only those
    requests are walked in order."""
    rows, n = warm_end.shape
    mask = np.zeros((rows, n), bool)
    mask[:, 0] = keep_warm < math.inf
    after_warm = (t0[1:] - warm_end[:, :-1]) > keep_warm
    after_cold = (t0[1:] - cold_end[:, :-1]) > keep_warm
    mask[:, 1:] = after_warm & after_cold
    for r, k in zip(*np.nonzero(after_warm & ~after_cold)):
        mask[r, k + 1] = not mask[r, k]
    return mask


def totals(nodes, t0, msg, z, dtype, prefetch=True):
    """Totals of one stream of requests through a DAG. ``nodes``: per step,
    in a topological order, a dict of cold/fetch/compute (median, sigma)
    pairs, ``keep_warm`` and ``preds``, a list of (position of the
    predecessor, seconds of the edge's transfer), each number a (rows,)
    array or scalar; ``z``: the (steps, n) normal blocks, one set per row:
    arrays of shape (rows, steps, n)."""
    z_cold, z_fetch, z_comp = (np.asarray(a, np.float32) for a in z)
    rows = z_cold.shape[0]
    t0 = np.asarray(t0).astype(dtype)
    m = np.asarray(msg, dtype)

    def draw(pair, zz):
        med, sig = (np.broadcast_to(np.asarray(x, np.float64), (rows,)) for x in pair)
        factor = np.exp(sig[:, None].astype(dtype) * zz.astype(dtype))
        return np.where(med[:, None] > 0, med[:, None].astype(dtype) * factor, dtype(0))

    def arrival(u, tr):
        tr = np.broadcast_to(np.asarray(tr, np.float64), (rows,))
        return end[u] + tr[:, None].astype(dtype)

    read = {u for node in nodes for u, _ in node["preds"]}
    end, depth, last = [], [], None
    for v, node in enumerate(nodes):
        cold = draw(node["cold"], z_cold[:, v])
        fetch = draw(node["fetch"], z_fetch[:, v])
        comp = draw(node["compute"], z_comp[:, v])
        if not node["preds"]:
            payload = np.broadcast_to(t0 + m / dtype(2), cold.shape)
            depth.append(0.0 if prefetch else math.inf)
        else:
            payload = functools.reduce(
                np.maximum, [arrival(u, tr) for u, tr in node["preds"]])
            depth.append(min(depth[u] for u, _ in node["preds"]) + 1.0)
        if depth[v] < math.inf:
            poke = t0 + dtype(depth[v]) * m
            warm_end = np.maximum(payload, poke + fetch) + comp
            cold_end = np.maximum(payload, poke + cold + fetch) + comp
        else:
            warm_end = payload + fetch + comp
            cold_end = payload + cold + fetch + comp
        end.append(np.where(cold_mask(t0, warm_end, cold_end, node["keep_warm"]),
                            cold_end, warm_end))
        if v not in read:
            last = end[v] if last is None else np.maximum(last, end[v])
    return (last - t0).astype(dtype)


def _rows(seeds, n_placements, steps, n):
    zs = [normals(s, steps, n) for s in seeds]
    # rows are (seed, placement), seed-major: every placement of a seed
    # shares that seed's draws
    return [np.repeat(np.stack([z[i] for z in zs]), n_placements, axis=0)
            for i in range(3)]


def _preds(cfg, v, placements, ins, seeds):
    """Step v's in-edges, each with its transfer per (seed, placement) row."""
    plat = {p["name"]: p for p in cfg["platforms"]}
    return [(u, np.tile([transfer_s(cfg, plat[pl[u]], plat[pl[v]])
                         for pl in placements], len(seeds)))
            for u in ins[v]]


def scorer_totals(cfg, mix, placements, drift, seeds, dtype=np.float64):
    """(placements, seeds * n) totals of one scorer decision: the drifted
    medians with the scorer's spread, the configuration's edges,
    never-cold platforms. ``placements``: platform names in listing
    order."""
    wf = cfg["workflow"]
    plats = [p["name"] for p in cfg["platforms"]]
    sigma, n = mix["scorer"]["sigma"], mix["n_requests"]
    P, ins = len(placements), in_edges(cfg)
    nodes = []
    for v, step in enumerate(wf):
        j = np.array([plats.index(pl[v]) for pl in placements])
        comp = step["compute"][0] * drift[0, v, j]
        fetch = step["fetch"][0] * drift[1, v, j]
        nodes.append({
            "cold": (0.0, 0.0), "keep_warm": math.inf,
            "fetch": (np.tile(fetch, len(seeds)), sigma),
            "compute": (np.tile(comp, len(seeds)), sigma),
            "preds": _preds(cfg, v, placements, ins, seeds),
        })
    t0 = np.arange(n) * cfg["interarrival_s"]
    out = totals(nodes, t0, cfg["msg_latency_s"], _rows(seeds, P, len(wf), n), dtype,
                 cfg["prefetch"])
    # rows (seed, placement) -> (placement, seed * n)
    return np.swapaxes(out.reshape(len(seeds), P, n), 0, 1).reshape(P, -1)


def transfer_s(cfg, src: dict, dst: dict) -> float:
    """The payload edge between two platforms: a direct local call where the
    destination takes synchronous traffic natively in the same region,
    else a PUT at the source's side and a GET in the destination region
    through the object store (per-op overhead plus size over bandwidth)."""
    if dst["native_prefetch"] and dst["allows_sync"] and src["region"] == dst["region"]:
        return cfg["msg_latency_s"] * 0.1
    ol, size = cfg["object_latency"], cfg["payload_size_bytes"]

    def op(a, b):
        same = a == b
        oh = ol["overhead_same"] if same else ol["overhead_cross"]
        return oh + size / (ol["bw_same"] if same else ol["bw_cross"])

    return op(src["region"], dst["region"]) + op(dst["region"], dst["region"])


def sweep_totals(cfg, mix, placements, seeds, dtype=np.float64):
    """(seeds, placements, n) totals of one sweep on the configuration's
    platforms. ``placements``: platform names in listing order."""
    wf, n, P = cfg["workflow"], mix["n_requests"], len(placements)
    plat = {p["name"]: p for p in cfg["platforms"]}
    ins = in_edges(cfg)
    nodes = []
    for v, step in enumerate(wf):
        ps = [plat[pl[v]] for pl in placements]
        if len({p["keep_warm_s"] for p in ps}) != 1:
            raise ValueError("placements of one step differ in keep_warm_s")
        nodes.append({
            "cold": (np.tile([p["cold_start"][0] for p in ps], len(seeds)),
                     np.tile([p["cold_start"][1] for p in ps], len(seeds))),
            "keep_warm": ps[0]["keep_warm_s"],
            "fetch": tuple(step["fetch"]),
            "compute": tuple(step["compute"]),
            "preds": _preds(cfg, v, placements, ins, seeds),
        })
    t0 = np.arange(n) * cfg["interarrival_s"]
    out = totals(nodes, t0, cfg["msg_latency_s"], _rows(seeds, P, len(wf), n), dtype,
                 cfg["prefetch"])
    return out.reshape(len(seeds), P, n)
