"""Driver of plan-sweep traffic: ``run_sweep`` calls back to back.

Set-up builds the configuration's app bank once, warms the fused sweep
program of every sampling plan the traffic draws (one sweep each), and
rewinds the memo to its state after the build. A request is one sweep
of every app x config under one of the cell's (stratifier, policy)
pairs, each pair once in every block of requests, in an order drawn
from the run's ``--seed``; a ``random``-policy sweep also draws a fresh selection
seed, so it picks new units and the memo fills them. Its work is the
(app, config) rows it returns.

The check, after the window, holds the program to the plain reference
(``bench/reference``): the bank and strata as the trial cells check
them; every row's estimate against the reference's picks, CPI and
weights; and the memo's fills over the window (which units, their CPI,
their charges) against the union of the reference's picks.
"""

from __future__ import annotations

import hashlib

import numpy as np

from bench.lib import bank
from bench.reference import selection

SPANS = ("setup", "setup.*", "window", "sweep")
WARM_SELECTION_SEED = 12345
ROW_GAP = 1e-4      # an estimate further than this from the reference's
ERR_GAP = 1e-3      # percentage points an error may lie from its estimate's


def draw(params: dict, seed: int, i: int) -> tuple[tuple, int]:
    """Request ``i``'s (stratifier, policy) pair and selection seed.

    Every run sends each pair once in every block of ``len(pairs)``
    requests, in an order the seed shuffles block by block, so every
    seed sends the same mix of plans."""
    pairs = params["pairs"]
    n = len(pairs)
    order = np.random.default_rng([int(seed), i // n]).permutation(n)
    pair = tuple(pairs[int(order[i % n])])
    rng = np.random.default_rng([int(seed), int(i)])
    sel = int(rng.integers(2 ** 31 - 1)) if pair[1] == "random" else 0
    return pair, sel


def _spec(apps, pair, sel):
    from repro.core.sampling.plan import (SamplingPlan, make_policy,
                                          make_stratifier)
    from repro.experiments import SweepSpec

    return SweepSpec(apps=apps, selection_seed=sel, plan=SamplingPlan(
        make_stratifier(pair[0]), make_policy(pair[1])))


def setup(ctx: dict) -> dict:
    state = bank.build(ctx)
    engine = state["engine"]
    engine.memo.cols_for(engine.configs)
    state["memo_build"] = engine.memo.state()
    return state


def warm(state: dict) -> None:
    """One sweep of every plan (the fused program of each compiles or
    loads), then the memo back to its state after the build."""
    from repro.core.sampling import plan as plan_mod
    from repro.experiments import run_sweep

    engine = state["engine"]
    for pair in state["params"]["pairs"]:
        sel = WARM_SELECTION_SEED if pair[1] == "random" else 0
        run_sweep(engine, _spec(state["apps"], tuple(pair), sel))
        if not plan_mod.last_sweep_dispatch()["fused"]:
            raise RuntimeError(f"plan {pair} did not run fused")
    rewind(state)


def request(state: dict, seed: int, i: int) -> dict:
    from repro.experiments import run_sweep

    pair, sel = draw(state["params"], seed, i)
    table = run_sweep(state["engine"], _spec(state["apps"], pair, sel))
    a = len(state["apps"])
    return {"work": len(table), "out": {
        "pair": pair, "sel": sel,
        "est": table.column("estimate").reshape(a, -1),
        "err": table.column("err_pct").reshape(a, -1)}}


def digest(outputs: list) -> str:
    """sha256 over every sweep's estimates and errors, in window order."""
    h = hashlib.sha256()
    for o in outputs:
        h.update(repr((o["pair"], o["sel"])).encode())
        h.update(np.ascontiguousarray(o["est"]).tobytes())
        h.update(np.ascontiguousarray(o["err"]).tobytes())
    return h.hexdigest()


def extract(state: dict) -> dict:
    """The build's arrays and the memo before and after the window."""
    memo = state["engine"].memo
    out = bank.extract(state)
    tree, _ = memo.state()
    before, _ = state["memo_build"]
    out["memo"] = {k: np.asarray(tree[k]) for k in ("mask", "cpi", "charges")}
    out["memo_build"] = {k: np.asarray(before[k])
                         for k in ("mask", "charges")}
    return out


# ------------------------------------------------------------------ check
def _strata_inputs(kind: str, prog: dict, ref: dict, a: int):
    """(labels, valid, baseline, feats, centroids, pool) of one app under
    stratifier ``kind``: the reference's own values with the program's
    (checked) strata and centroids; ``pool`` maps phase-1 positions to
    regions."""
    if kind == "bbv":
        lab = prog["bbv_labels"][a]
        return (lab, np.ones(len(lab), bool), ref["census"][a][0],
                ref["bbv_z"][a], prog["bbv_centroids"][a], None)
    if kind == "rfv":
        lab = prog["rfv_labels"][a]
        return (lab, np.ones(len(lab), bool), ref["cpi0_1"][a],
                ref["rfv_z"][a], prog["rfv_centroids"][a], ref["idx1"][a])
    lab = prog["dg_labels"][a]
    return (lab, np.ones(len(lab), bool), ref["cpi0_1"][a], None, None,
            ref["idx1"][a])


def reference_sweep(pair, sel, prog, ref, L: int, dtype=np.float64):
    """One sweep by the reference: per app its picked units and their
    near-ties, and (A, C) estimates, errors and slack, the most an
    estimate can move if float32 resolves near-ties the other way."""
    kind, policy = pair
    a_n = len(ref["census"])
    u = np.random.default_rng(sel).random((a_n, L)) \
        if policy == "random" else [None] * a_n
    picks, ties, ests, errs, slack = [], [], [], [], []
    for a in range(a_n):
        lab, valid, base, feats, cents, pool = _strata_inputs(kind, prog,
                                                              ref, a)
        local, occ, alts = selection.pick_app(policy, lab, valid, base,
                                              feats, cents, L, u=u[a],
                                              dtype=dtype)
        unit = (lambda i: i) if pool is None else \
            (lambda i: np.asarray(pool)[i])
        units = unit(local)
        counts = np.bincount(np.asarray(lab), minlength=L)[:L]
        w = counts / len(lab)
        census = ref["census"][a]
        truth = census.mean(axis=1)
        est, err = selection.estimate(census[:, units], w, occ, truth,
                                      dtype=dtype)
        dev = np.zeros(len(census))
        for h in np.flatnonzero(occ):
            if len(alts[h]):
                moved = np.abs(census[:, unit(alts[h])]
                               - census[:, units[h], None]).max(1)
                dev += w[h] * moved
        picks.append(units[occ])
        ties.append(np.concatenate([unit(alts[h]) for h in range(L)]
                                   + [units[[h for h in range(L)
                                             if occ[h] and len(alts[h])]]]))
        ests.append(est)
        errs.append(err)
        slack.append(dev / w[occ].sum())
    return dict(picks=picks, ties=ties, est=np.stack(ests),
                err=np.stack(errs), slack=np.stack(slack),
                truth=np.stack([c.mean(axis=1) for c in ref["census"]]))


def view(outputs: list, prog: dict, ref: dict, L: int,
         dtype=np.float64) -> dict:
    """What the window's sweeps produce, as the reference computes it in
    ``dtype``: each sweep's (A, C) estimates, errors and slack, and per
    (app, config) the units the window's picks add to the memo: for
    sure, and those that near-ties may add or leave out."""
    a_n, c_n = len(ref["census"]), len(ref["census"][0])
    cache: dict = {}
    sweeps = []
    sure = [set() for _ in range(a_n)]
    maybe = [set() for _ in range(a_n)]
    for o in outputs:
        key = (tuple(o["pair"]), o["sel"])
        if key not in cache:
            cache[key] = reference_sweep(key[0], key[1], prog, ref, L,
                                         dtype=dtype)
        r = cache[key]
        sweeps.append(r)
        for a in range(a_n):
            t = set(np.asarray(r["ties"][a]).tolist())
            sure[a].update(set(np.asarray(r["picks"][a]).tolist()) - t)
            maybe[a].update(t)
    built = [set(np.asarray(i).tolist()) for i in ref["idx1"]]
    fills = [[sure[a] - (built[a] if c == 0 else set())
              for c in range(c_n)] for a in range(a_n)]
    maybe = [[maybe[a] - (built[a] if c == 0 else set())
              for c in range(c_n)] for a in range(a_n)]
    values = {(a, c): ref["census"][a][c][sorted(fills[a][c])]
              .astype(dtype).astype(np.float64)
              for a in range(a_n) for c in range(c_n)}
    charges = np.asarray([[len(f) for f in row] for row in fills])
    return dict(est=[r["est"] for r in sweeps], err=[r["err"] for r in sweeps],
                slack=[r["slack"] for r in sweeps], fills=fills, maybe=maybe,
                values=values, charges=charges,
                truth=sweeps[0]["truth"] if sweeps else None)


def program_view(outputs: list, prog: dict, a_n: int) -> dict:
    """The same from the program: its estimates, errors, memo fills and
    the charges of those fills."""
    m, b = prog["memo"], prog["memo_build"]
    c_n = outputs[0]["est"].shape[1] if outputs else 0
    fills = []
    values = {}
    for a in range(a_n):
        row = []
        for c in range(c_n):
            units = np.flatnonzero(m["mask"][a, c] & ~b["mask"][a, c])
            row.append(set(units.tolist()))
            values[(a, c)] = m["cpi"][a, c][units].astype(np.float64)
        fills.append(row)
    charges = (m["charges"] - b["charges"])[:a_n, :c_n]
    return dict(est=[o["est"] for o in outputs],
                err=[o["err"] for o in outputs], fills=fills,
                values=values, charges=charges)


def compare(got: dict, want: dict, ref: dict) -> dict:
    """Readings of a view against the reference's.

    ``rows_off``: of the app whose rows fare worst, the share of its
    rows over the window whose estimate lies further than ``ROW_GAP``
    plus the near-ties' slack from the reference's, or whose error is
    not ``100 |estimate - truth| / truth`` to ``ERR_GAP`` points.
    ``memo_set_off``: units filled that no pick explains, and sure
    picks not filled, over the sure picks. ``memo_value_gap``: the
    largest relative gap of a filled unit's CPI from the census.
    ``charge_gap``: charges that differ from one per unit filled."""
    a_n = len(ref["census"])
    off = np.zeros(a_n)
    rows = 0
    truth = want["truth"]
    for e, err, r, sl in zip(got["est"], got["err"], want["est"],
                             want["slack"]):
        e = np.asarray(e, np.float64)
        bad = np.abs(e - r) > ROW_GAP * np.abs(r) + sl
        due = 100.0 * np.abs(e - truth) / truth
        bad |= ~(np.abs(np.asarray(err, np.float64) - due) <= ERR_GAP)
        off += bad.sum(1)
        rows += bad.shape[1]
    sym = total = 0
    value_gap = 0.0
    charge_off = 0
    for a, row in enumerate(want["fills"]):
        for c, w in enumerate(row):
            g = got["fills"][a][c]
            sym += len(g - w - want["maybe"][a][c]) + len(w - g)
            total += len(w)
            if g:
                r = ref["census"][a][c][sorted(g)]
                value_gap = max(value_gap,
                                bank.gap(got["values"][(a, c)], r))
            charge_off += abs(int(got["charges"][a, c]) - len(g))
    return dict(rows_off=float(off.max() / max(rows, 1)),
                memo_set_off=sym / max(total, 1),
                memo_value_gap=value_gap,
                charge_gap=charge_off / max(total, 1))


def readings(config: dict, params: dict, prog: dict, ref: dict,
             outputs: list, seed: int, dtype=None) -> dict:
    """Readings of every sweep of the window and of the memo's fills
    against the reference. With ``dtype`` (bfloat16) the reference in
    that precision stands in for the program: the control."""
    if not outputs:
        return {}
    L = int(config["num_strata"])
    want = view(outputs, prog, ref, L)
    got = program_view(outputs, prog, len(ref["pops"])) if dtype is None \
        else view(outputs, prog, ref, L, dtype=dtype)
    return compare(got, want, ref)


def rewind(state: dict) -> None:
    """Back to the memo as the build left it."""
    state["engine"].memo.load_state(*state["memo_build"])


def check(config: dict, params: dict, prog: dict, outputs: list,
          seed: int) -> list:
    """[(name, reading, limit)] of every number compared."""
    return bank.check(readings, config, params, prog, outputs, seed)
