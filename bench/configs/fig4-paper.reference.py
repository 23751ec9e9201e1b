"""Plain reference of GeoFF's workflow simulation for the Fig-4 chain.

A straightforward implementation of the semantics the simulator documents,
written from them and importing nothing of the program:

- request k of a stream arrives at ``t0[k] = k * interarrival``; the first
  step's payload lands at ``t0 + msg / 2``, every later step's at its
  predecessor's end plus the edge's transfer;
- with pre-fetching, step v is poked at ``t0 + v * msg`` (the poke cascades
  one message per hop) and prepares from there: a warm instance is ready at
  ``poke + fetch``, a cold one at ``poke + cold + fetch``; it starts at the
  later of its payload and its preparation and ends ``compute`` later;
- an instance is cold when the request arrives more than ``keep_warm_s``
  after the previous request's end on that (step, platform), and the first
  request always finds it cold unless ``keep_warm_s`` is infinite;
- a request's total is the last step's end minus its arrival.

Draws follow the simulator's common-random-numbers contract: the seed
``s`` is the raw threefry key ``[s >> 32, s & 0xffffffff]``, split into
three streams (cold, fetch, compute), each a (steps, requests) block of
float32 standard normals; a draw is ``median * exp(sigma * z)``, and a
median of 0 draws 0. The normals are drawn here with ``jax.random`` from
the seed; everything after them is numpy in the dtype given (float64 for
the reference, bfloat16 for its control).
"""

from __future__ import annotations

import math

import numpy as np


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


def chain(nodes, t0, msg, z, dtype):
    """Totals of one stream of requests through a chain. ``nodes``: per
    step a dict of cold/fetch/compute (median, sigma) pairs, ``keep_warm``
    and ``transfer_in`` (seconds from the previous step; unused for the
    first), each a (rows,) array or scalar; ``z``: the (steps, n) normal
    blocks, one set per row: arrays of shape (rows, steps, n)."""
    z_cold, z_fetch, z_comp = (np.asarray(a, np.float32) for a in z)
    rows = z_cold.shape[0]
    t0 = np.asarray(t0).astype(dtype)
    m = np.asarray(msg, dtype)

    def draw(pair, zz):
        med, sig = (np.broadcast_to(np.asarray(x, np.float64), (rows,)) for x in pair)
        factor = np.exp(sig[:, None].astype(dtype) * zz.astype(dtype))
        return np.where(med[:, None] > 0, med[:, None].astype(dtype) * factor, dtype(0))

    end = None
    for v, node in enumerate(nodes):
        cold = draw(node["cold"], z_cold[:, v])
        fetch = draw(node["fetch"], z_fetch[:, v])
        comp = draw(node["compute"], z_comp[:, v])
        if v == 0:
            payload = np.broadcast_to(t0 + m / dtype(2), cold.shape)
        else:
            tr = np.broadcast_to(np.asarray(node["transfer_in"], np.float64), (rows,))
            payload = end + tr[:, None].astype(dtype)
        poke = t0 + dtype(v) * m
        warm_end = np.maximum(payload, poke + fetch) + comp
        cold_end = np.maximum(payload, poke + cold + fetch) + comp
        kw = node["keep_warm"]
        end = np.where(cold_mask(t0, warm_end, cold_end, kw), cold_end, warm_end)
    return (end - t0).astype(dtype)


def _rows(seeds, n_placements, steps, n):
    zs = [normals(s, steps, n) for s in seeds]
    # rows are (seed, placement), seed-major: every placement of a seed
    # shares that seed's draws
    return [np.repeat(np.stack([z[i] for z in zs]), n_placements, axis=0)
            for i in range(3)]


def scorer_totals(cfg, mix, placements, drift, seeds, dtype=np.float64):
    """(placements, seeds * n) totals of one scorer decision: the drifted
    medians with the scorer's spread, the configuration's edges,
    never-cold platforms."""
    wf = cfg["workflow"]
    plats = [p["name"] for p in cfg["platforms"]]
    plat = {p["name"]: p for p in cfg["platforms"]}
    sigma, n = mix["scorer"]["sigma"], mix["n_requests"]
    P = len(placements)
    nodes = []
    for v, step in enumerate(wf):
        j = np.array([plats.index(pl[v]) for pl in placements])
        comp = step["compute"][0] * drift[0, v, j]
        fetch = step["fetch"][0] * drift[1, v, j]
        tr = [0.0 if v == 0 else transfer_s(cfg, plat[pl[v - 1]], plat[pl[v]])
              for pl in placements]
        nodes.append({
            "cold": (0.0, 0.0), "keep_warm": math.inf,
            "fetch": (np.tile(fetch, len(seeds)), sigma),
            "compute": (np.tile(comp, len(seeds)), sigma),
            "transfer_in": np.tile(tr, len(seeds)),
        })
    t0 = np.arange(n) * cfg["interarrival_s"]
    out = chain(nodes, t0, cfg["msg_latency_s"], _rows(seeds, P, len(wf), n), dtype)
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
    platforms."""
    wf, n, P = cfg["workflow"], mix["n_requests"], len(placements)
    plat = {p["name"]: p for p in cfg["platforms"]}
    nodes = []
    for v, step in enumerate(wf):
        ps = [plat[pl[v]] for pl in placements]
        nodes.append({
            "cold": (np.tile([p["cold_start"][0] for p in ps], len(seeds)),
                     np.tile([p["cold_start"][1] for p in ps], len(seeds))),
            "keep_warm": ps[0]["keep_warm_s"],
            "fetch": tuple(step["fetch"]),
            "compute": tuple(step["compute"]),
            "transfer_in": np.tile(
                [0.0 if v == 0 else transfer_s(cfg, plat[pl[v - 1]], plat[pl[v]])
                 for pl in placements], len(seeds)),
        })
        if len({p["keep_warm_s"] for p in ps}) != 1:
            raise ValueError("placements of one step differ in keep_warm_s")
    t0 = np.arange(n) * cfg["interarrival_s"]
    out = chain(nodes, t0, cfg["msg_latency_s"], _rows(seeds, P, len(wf), n), dtype)
    return out.reshape(len(seeds), P, n)
