"""Fleet cells: building the replicate batch, and the comparison of what
the timed path produced with the plain reference (`reference/`).

A fleet cell runs B replicates of one deployment under one mix; members
differ only by seed.  After the window, every member is replayed by the
reference from the same seed through the same schedule of epochs and
control-plane steps, `check_block` members at a time, and compared:

  state_mismatch  integer elements that differ: every integer leaf of the
                  member's final state (key-value tables, logs, roles,
                  terms, timers, queues, wiring, digest rack) and every
                  integer field of every epoch's report.  Exact.
  price_gap       largest relative gap of the final spot prices, the
                  float state that the market walk carries.
  cost_gap        largest relative gap of the accrued cost and of each
                  epoch's cost, read-latency sum and maximum.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

import harness
from traffic import generator

INT_FIELDS = ("reads_arrived", "writes_arrived", "reads_served",
              "writes_committed", "n_secretaries", "n_observers",
              "leader_changes", "no_leader_ticks", "killed",
              "obs_reads_served", "obs_rerouted", "n_obs_digest")
HIST_FIELDS = ("write_lat_p95", "write_lat_p99", "read_lat_p95",
               "read_lat_p99", "obs_stale_p95", "obs_stale_p99")
FLOAT_FIELDS = ("cost", "read_lat_mean", "read_lat_max")


def build_fleet(cfg: dict, mix: dict, seed: int, members: int, *,
                manage: bool):
    """The program's FleetSim: `members` replicates of the deployment
    under the mix, seeds derived from `seed`."""
    from repro.core.fleet import FleetSim, MemberSpec
    generator.require_supported(mix)
    cc = harness.cluster_config(cfg)
    dt = cfg["digest_tier"]
    specs = [MemberSpec(
        cfg=cc, write_rate=mix["write_rate"], read_rate=mix["read_rate"],
        phi=mix["phi"], seed=s, manage_resources=manage,
        market=mix["market"], keypop=generator.KeyPopularity(mix),
        n_observers=dt["n_observers"],
        staleness_bound=dt["staleness_bound"],
        ae_interval=dt["ae_interval"])
        for s in harness.member_seeds(seed, members)]
    fleet = FleetSim(specs)
    for m in fleet.members:
        harness.require_node_model(cfg, m.static, m.cfg_c)
    return fleet


class FleetDriver:
    """What both fleet drivers share: the fleet, the timed loop over the
    driver's `step` (one dispatch, `step` epochs), and the check.  A
    driver gives `manage`, `step()` and `schedule()`: the reference's
    steps for everything the run did."""
    manage = True

    def __init__(self, cell: dict, cfg: dict, mix: dict, seed: int):
        self.cell, self.cfg, self.mix, self.seed = cell, cfg, mix, seed
        self.B = cell["members"]
        self.fleet = build_fleet(cfg, mix, seed, self.B, manage=self.manage)
        self.T = self.fleet.shapes.T

    def window(self, seconds: float) -> dict:
        d2h0, done = self.fleet.d2h_bytes, 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            done += self.step()
        wall = time.perf_counter() - t0
        return {
            "metrics": {"cluster_ticks_per_s": self.B * self.T * done / wall},
            "counters": {"epochs": done, "wall_s": wall,
                         "d2h_bytes": self.fleet.d2h_bytes - d2h0},
            "attempted": self.B * done, "failed": 0}

    def release(self) -> None:
        """Copy what the check needs to the host and free the fleet."""
        self.got = snapshot(self.fleet, self.B)
        self.fleet = None

    def check(self, fdt=None) -> list:
        """Every member against the reference, replayed `check_block`
        members at a time so that the reference fits the chip."""
        mismatch, price, cost = 0, 0.0, 0.0
        step = self.cell["check_block"]
        for lo in range(0, self.B, step):
            idx = list(range(lo, min(lo + step, self.B)))
            ref = replay(self.cfg, self.mix, self.seed, self.B, idx,
                         self.schedule(), fdt=fdt)
            m, p, c = compare([self.got[0][i] for i in idx],
                              [self.got[1][i] for i in idx], *ref)
            mismatch += m
            price, cost = harness.worst(price, p), harness.worst(cost, c)
        lim = self.cell["limits"]
        return [harness.Check("state_mismatch", mismatch,
                              lim["state_mismatch"]),
                harness.Check("price_gap", price, lim["price_gap"]),
                harness.Check("cost_gap", cost, lim["cost_gap"])]


def snapshot(fleet, members: int) -> Tuple[List[Dict], List]:
    """Host copies of every member's final state and reports."""
    import jax
    states = jax.tree.map(lambda x: np.asarray(x)[:members], fleet.state)
    per = [{k: v[j] for k, v in states.items()} for j in range(members)]
    return per, [m.reports for m in fleet.members[:members]]


def report_row(dg: Dict) -> Dict:
    """The report fields of one reference epoch digest (numpy leaves)."""
    from reference.sim import hist_percentile
    wl, rl, sl = (np.asarray(dg[k]) for k in
                  ("write_lat_hist", "read_lat_hist", "obs_stale_hist"))
    served = int(dg["reads_served"])
    return {
        **{k: int(dg[k]) for k in INT_FIELDS if k in dg},
        "writes_committed": int(wl.sum()),
        "write_lat_p95": hist_percentile(wl, 95),
        "write_lat_p99": hist_percentile(wl, 99),
        "read_lat_p95": hist_percentile(rl, 95),
        "read_lat_p99": hist_percentile(rl, 99),
        "obs_stale_p95": hist_percentile(sl, 95),
        "obs_stale_p99": hist_percentile(sl, 99),
        "cost": float(dg["cost_delta"]),
        "read_lat_mean": float(dg["read_lat_sum"] / max(served, 1)),
        "read_lat_max": float(dg["read_lat_max"]),
    }


def replay(cfg: dict, mix: dict, seed: int, members: int,
           idx: Sequence[int], schedule: Sequence, fdt=None):
    """Run the reference for members `idx` through `schedule`:
    ("epoch", managed) or ("lease", secretaries, observers) steps.
    Returns (final states, reports) as numpy, member by member."""
    import jax
    import jax.numpy as jnp
    from reference import control, sim

    fdt = fdt or jnp.float32
    c = cfg["cluster"]
    model = sim.Model(cfg, write_rate=mix["write_rate"],
                      read_rate=mix["read_rate"], phi=mix["phi"],
                      key_cdf=generator.key_cdf(mix, c["key_space"]),
                      key_zipf=True, fdt=fdt)
    seeds = [harness.member_seeds(seed, members)[i] for i in idx]
    ctl = [control.Controller(model, s) for s in seeds]
    keys = [jax.random.PRNGKey(s) for s in seeds]
    st = jax.tree.map(lambda *x: jnp.stack(x),
                      *[model.init_state() for _ in seeds])
    # the inputs go in as an argument (`Model.with_inputs`), not folded in
    epoch = jax.jit(jax.vmap(lambda s, k, inp: model.with_inputs(inp).epoch(
        s, k), in_axes=(0, 0, None)))
    reports: List[List[Dict]] = [[] for _ in seeds]
    for step in schedule:
        if step[0] == "epoch":
            subs = []
            for j in range(len(keys)):
                keys[j], sub = jax.random.split(keys[j])
                subs.append(sub)
            st, dg = epoch(st, jnp.stack(subs), model.inp)
            dg = jax.tree.map(np.asarray, dg)
            rows = [{k: v[j] for k, v in dg.items()}
                    for j in range(len(seeds))]
            for j, row in enumerate(rows):
                reports[j].append(report_row(row))
            if step[1]:
                wired = [ctl[j].lease(rows[j]["role"], rows[j]["alive"],
                                      *ctl[j].decide(rows[j]))
                         for j in range(len(seeds))]
                st = _write_roles(st, wired)
        else:
            host = jax.tree.map(np.asarray, {k: st[k] for k in
                                             ("role", "alive")})
            wired = [ctl[j].lease(host["role"][j], host["alive"][j],
                                  step[1], step[2])
                     for j in range(len(seeds))]
            st = _write_roles(st, wired)
    st = jax.tree.map(np.asarray, st)
    return [{k: v[j] for k, v in st.items()} for j in range(len(seeds))], \
        reports


def _write_roles(st, wired):
    import jax.numpy as jnp
    names = ("role", "alive", "sec_of", "obs_of")
    return dict(st, **{n: jnp.asarray(np.stack([w[i] for w in wired]))
                       for i, n in enumerate(names)})


def compare(got_states, got_reports, ref_states, ref_reports
            ) -> Tuple[int, float, float]:
    """(state_mismatch, price_gap, cost_gap) over the members given."""
    mismatch, price, cost = 0, 0.0, 0.0
    for gs, gr, rs, rr in zip(got_states, got_reports, ref_states,
                              ref_reports):
        ints = harness.int_leaves(rs)
        missing = [k for k in ints if k not in gs]
        mismatch += sum(rs[k].size for k in missing)
        mismatch += harness.int_mismatches(
            gs, rs, [k for k in ints if k not in missing])
        price = harness.worst(price, harness.rel_gap(gs["spot_price"],
                                                     rs["spot_price"]))
        cost = harness.worst(cost, harness.rel_gap(gs["cost_accrued"],
                                                   rs["cost_accrued"]))
        if len(gr) != len(rr):
            mismatch += abs(len(gr) - len(rr)) * len(INT_FIELDS)
        for g, r in zip(gr, rr):
            mismatch += sum(int(getattr(g, k)) != r[k] for k in INT_FIELDS)
            mismatch += sum(not _same(getattr(g, k), r[k])
                            for k in HIST_FIELDS)
            for k in FLOAT_FIELDS:
                cost = harness.worst(cost,
                                     harness.rel_gap(getattr(g, k), r[k]))
    return mismatch, price, cost


def _same(a: float, b: float) -> bool:
    if math.isnan(a) or math.isnan(b):
        return math.isnan(a) and math.isnan(b)
    return abs(a - b) <= 1e-9 * max(1.0, abs(b))
