"""The shared plain reference of the placement sweep (``bench/sweep_reference.py``)
and the candidate placements of the sweep's mixes.

The reference runs the workflow DAG's recurrence; over a chain it gives,
bit for bit, the totals of the chain-only reference it replaced, which is
kept here as it was (``chain_*``) to pin that. The placement kinds give
the candidates each mix states."""

import itertools
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, traffic  # noqa: E402
from bench import sweep_reference as R  # noqa: E402

PS = harness.system("placement_sweep")
FIG4 = harness.config("fig4-paper")
SEEDS = (1, 2, 2**40 + 77)  # the seeds the correctness tests run


# -- the chain-only reference, as it was before the DAG -------------------------
def chain(nodes, t0, msg, z, dtype):
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
        end = np.where(R.cold_mask(t0, warm_end, cold_end, kw), cold_end, warm_end)
    return (end - t0).astype(dtype)


def chain_scorer_totals(cfg, mix, placements, drift, seeds, dtype=np.float64):
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
        tr = [0.0 if v == 0 else R.transfer_s(cfg, plat[pl[v - 1]], plat[pl[v]])
              for pl in placements]
        nodes.append({
            "cold": (0.0, 0.0), "keep_warm": math.inf,
            "fetch": (np.tile(fetch, len(seeds)), sigma),
            "compute": (np.tile(comp, len(seeds)), sigma),
            "transfer_in": np.tile(tr, len(seeds)),
        })
    t0 = np.arange(n) * cfg["interarrival_s"]
    out = chain(nodes, t0, cfg["msg_latency_s"], R._rows(seeds, P, len(wf), n), dtype)
    return np.swapaxes(out.reshape(len(seeds), P, n), 0, 1).reshape(P, -1)


def chain_sweep_totals(cfg, mix, placements, seeds, dtype=np.float64):
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
                [0.0 if v == 0 else R.transfer_s(cfg, plat[pl[v - 1]], plat[pl[v]])
                 for pl in placements], len(seeds)),
        })
    t0 = np.arange(n) * cfg["interarrival_s"]
    out = chain(nodes, t0, cfg["msg_latency_s"], R._rows(seeds, P, len(wf), n), dtype)
    return out.reshape(len(seeds), P, n)


def small(m):
    """The correctness tests' size of a sweep mix."""
    if m["call"] == "scorer":
        fewer = dict(m["placements"], count=8)
        return dict(m, n_requests=64, sweep_seeds=2, placements=fewer)
    return dict(m, n_requests=2048)


@pytest.mark.parametrize("mix", ["decide", "throughput"])
@pytest.mark.parametrize("seed", SEEDS)
def test_dag_reference_is_the_chain_reference_bit_for_bit(mix, seed):
    m = small(harness.mix(mix))
    cands = PS.placements(FIG4, m)
    for call in (0, 5):
        seeds = traffic.sweep_seeds(seed, call, m["sweep_seeds"])
        if m["call"] == "scorer":
            drift = traffic.drift_factors(FIG4, seed, call, len(FIG4["platforms"]))
            want = chain_scorer_totals(FIG4, m, cands, drift, seeds)
            got = R.scorer_totals(FIG4, m, cands, drift, seeds)
        else:
            want = chain_sweep_totals(FIG4, m, cands, seeds)
            got = R.sweep_totals(FIG4, m, cands, seeds)
        assert got.dtype == want.dtype == np.float64
        assert got.tobytes() == want.tobytes()


def test_fig4_reference_keeps_its_interface():
    ref = harness.reference("fig4-paper")
    assert ref.scorer_totals is R.scorer_totals and ref.sweep_totals is R.sweep_totals
    assert ref.transfer_s is R.transfer_s
    assert R.edges(FIG4) == [("check", "virus"), ("virus", "ocr"), ("ocr", "e_mail")]
    assert R.in_edges(FIG4) == [[], [0], [1], [2]]


# -- the recurrence's rules, by hand ------------------------------------------------
def fixed(x):
    return (x, 0.0)  # a draw with no spread is its median


def test_dag_rules_on_a_hand_worked_request():
    """a -> b -> d and c -> d, no spread, never cold, one request at t0 = 0,
    msg 0.1: d is poked after one message (the least depth of b and c, plus
    one), joins the later of b's and c's ends plus each edge's transfer, and
    the total is d's end."""
    def node(fetch, compute, preds):
        return {"cold": fixed(5.0), "fetch": fixed(fetch), "compute": fixed(compute),
                "keep_warm": math.inf, "preds": preds}

    nodes = [node(0.0, 1.0, []),  # a: payload 0.05, ends 1.05
             node(0.5, 1.0, [(0, 0.2)]),  # b: payload 1.25, poked 0.1, ends 2.25
             node(3.0, 0.5, []),  # c: poked 0, ready 3.0, ends 3.5
             node(0.4, 1.0, [(1, 0.3), (2, 0.1)])]  # d: joins max(2.55, 3.6)
    z = [np.zeros((1, 4, 1), np.float32)] * 3
    got = R.totals(nodes, [0.0], 0.1, z, np.float64)
    assert got[0].tolist() == pytest.approx([4.6])
    # pre-fetch off: every step starts at its payload plus its fetch
    got = R.totals(nodes, [0.0], 0.1, z, np.float64, prefetch=False)
    # a 1.05; b 1.25 + 0.5 + 1 = 2.75; c 0.05 + 3 + 0.5 = 3.55;
    # d max(3.05, 3.65) + 0.4 + 1
    assert got[0].tolist() == pytest.approx([5.05])


def test_each_step_goes_cold_on_its_own():
    """Two sources, keep_warm 1 s, requests at 0 and 3 s, both first
    requests cold. Source a (cold 0.5) ends at 1.5 and idles 1.5 s: cold
    again, ending at 4.5. Source b (cold 2.0) ends at 3.0 and idles 0 s:
    warm, ending at 4.0. The sink joins them and runs 1 s."""
    def node(cold, preds):
        return {"cold": fixed(cold), "fetch": fixed(0.0), "compute": fixed(1.0),
                "keep_warm": 1.0, "preds": preds}

    nodes = [node(0.5, []), node(2.0, []), node(0.0, [(0, 0.0), (1, 0.0)])]
    z = [np.zeros((1, 3, 2), np.float32)] * 3
    got = R.totals(nodes, [0.0, 3.0], 0.0, z, np.float64)
    assert got[0].tolist() == pytest.approx([4.0, 5.5 - 3.0])


def test_edges_must_name_steps_and_point_forward():
    cfg = dict(FIG4, edges=[["check", "virus"], ["ocr", "virus"]])
    with pytest.raises(ValueError, match="topological"):
        R.in_edges(cfg)
    with pytest.raises(ValueError, match="topological"):
        PS.check_listing(cfg)
    cfg = dict(FIG4, edges=[["check", "spam"]])
    with pytest.raises(ValueError, match="names no step"):
        R.in_edges(cfg)
    with pytest.raises(ValueError, match="lacks"):
        PS.check_listing(cfg)
    PS.check_listing(dict(FIG4, edges=[["check", "ocr"], ["virus", "e_mail"]]))


# -- candidate placements -----------------------------------------------------------
PLATS = [p["name"] for p in FIG4["platforms"]]
TF, GCF, LUS, LEU = PLATS


def test_chain_kinds_give_the_candidates_they_gave():
    assert PS.placements(FIG4, harness.mix("throughput")) == [
        [TF, TF, LUS, LUS], [TF, GCF, GCF, LUS],
        [TF, LUS, LUS, LUS], [TF, GCF, LEU, LUS],
    ]
    free = itertools.product(PLATS, repeat=3)
    assert PS.placements(FIG4, harness.mix("decide")) == [[TF, *c] for c in free][:32]


def test_rotate_groups_candidates():
    mix = {"placements": {"kind": "rotate_groups", "count": 4,
                          "groups": [["virus", "ocr"], ["e_mail"]]}}
    # candidate i: every step of group i mod 2 to platform i mod 4, the
    # rest where the configuration places them
    assert PS.placements(FIG4, mix) == [
        [TF, TF, TF, LUS],
        [TF, GCF, LUS, GCF],
        [TF, LUS, LUS, LUS],
        [TF, GCF, LUS, LEU],
    ]


@pytest.mark.parametrize("groups,count,match", [
    ([["ocr"]], 5, "repeats"),  # candidates 0 and 4 both move ocr to the edge
    ([["virus"], ["e_mail"]], 9, "repeats"),
    ([["spam"]], 2, "lacks"),
])
def test_rotate_groups_refuses_what_it_cannot_give(groups, count, match):
    mix = {"placements": {"kind": "rotate_groups", "count": count, "groups": groups}}
    with pytest.raises(ValueError, match=match):
        PS.placements(FIG4, mix)
