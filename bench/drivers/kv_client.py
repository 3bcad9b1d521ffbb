"""Driver: one closed-loop client of the KV service (`BWKVService`) over
one simulated cluster (`BWRaftSim`), requests from the mix's stream.
The cluster's own random streams start from the cell's `cluster_seed`;
the run's seed draws the requests.

Set-up elects a leader through the first put, leases the cell's fixed
`roles` = (secretaries, observers), then issues the stream's other
warm-up requests, so every program the window uses is compiled.  The
window issues the following requests one after the other until its time
is up.  An operation is one request; one that times out, or a get with
no leader, fails and counts as a miss in the latency tail.

The check replays every request the run issued, warm-up included, on
the reference client (`reference/kvclient.py`) and compares:

  answer_mismatch  requests whose outcome, log position or fence,
                   value read, or latency in ticks differs;
  state_mismatch   integer elements of the final cluster state that
                   differ (key-value tables, logs, roles, digest rack);
  stale_reads      gets that did not return the last acknowledged put
                   to their key (a plain dictionary of the acks);
  price_gap        largest relative gap of the final spot prices;
  cost_gap         largest relative gap of the accrued cost and the
                   read-latency sum and maximum.
"""
from __future__ import annotations

import time

import numpy as np
import jax

import harness
from traffic import generator

FAILED = "failed"


class Driver:
    spans = ("put", "get")

    def __init__(self, cell: dict, cfg: dict, mix: dict, seed: int):
        from repro.core.runtime import BWRaftSim
        from repro.kvstore.service import BWKVService
        self.cell, self.cfg, self.mix, self.seed = cell, cfg, mix, seed
        generator.require_supported(mix)
        dt = cfg["digest_tier"]
        self.sim = BWRaftSim(
            harness.cluster_config(cfg), write_rate=0.0, read_rate=0.0,
            phi=0.0, seed=cell["cluster_seed"],
            manage_resources=False, n_observers=dt["n_observers"],
            staleness_bound=dt["staleness_bound"],
            ae_interval=dt["ae_interval"])
        harness.require_node_model(cfg, self.sim.static, self.sim.cfg_c)
        self.svc = BWKVService(self.sim, timeout_ticks=cell["timeout_ticks"])
        self.ops = generator.client_ops(mix, seed)
        self.answers = []          # (status, revision, value, ticks)
        self.leased_after = 1      # roles are leased after the first put

    def _request(self, op) -> float:
        """Issue one request; returns its wall seconds (inf if failed)."""
        from repro.kvstore.service import NotLeader, Timeout
        kind, key, value = op
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(kind):
                if kind == "put":
                    r = self.svc.put(key, value)
                    ans = ("ok", r.revision, 0, r.latency_ticks)
                else:
                    got, fence = self.svc.get(key)
                    ans = ("ok", fence, got, self.svc.read_latencies[-1])
        except (Timeout, NotLeader):
            ans = (FAILED, -1, 0, -1)
        wall = time.perf_counter() - t0
        self.answers.append(ans)
        return wall if ans[0] == "ok" else float("inf")

    def warmup(self) -> None:
        n = generator.warmup_ops(self.mix)
        self._request(self.ops[0])
        self.sim.lease_fixed(*self.cell["roles"])
        for op in self.ops[1:n]:
            self._request(op)
        jax.block_until_ready(self.sim.state)

    def window(self, seconds: float) -> dict:
        tick0 = int(self.sim.state["tick"])
        walls = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if len(self.answers) >= len(self.ops):
                raise RuntimeError("the mix's request stream ran out; "
                                   "raise its 'ops'")
            walls.append(self._request(self.ops[len(self.answers)]))
        wall = time.perf_counter() - t0
        done = sum(w != float("inf") for w in walls)
        ticks = int(self.sim.state["tick"]) - tick0
        return {
            "metrics": {"kv_ops_per_s": done / wall,
                        "kv_p95_ms": 1000.0 * harness.percentile(walls, 95)},
            "counters": {"requests": len(walls), "wall_s": wall,
                         "ticks": ticks},
            "attempted": len(walls), "failed": len(walls) - done}

    def release(self) -> None:
        self.final = jax.tree.map(np.asarray, self.sim.state)
        self.sim = self.svc = None

    def check(self, fdt=None) -> list:
        return self.compare(self.answers, self.final, *self.replay(fdt))

    def fault_readings(self) -> dict:
        """The checks with a fault planted in the reference put in the
        program's place: `frozen`, every tick returns its state
        unchanged; `altered`, every get answers its value plus one."""
        ref = self.replay()
        out = {}
        for fault in ("frozen", "altered"):
            out[fault] = {c.name: c.value for c in
                          self.compare(*self.replay(fault=fault), *ref)}
        return out

    def replay(self, fdt=None, fault=None):
        """The reference's answers to every request the run issued, and
        its final state."""
        import jax.numpy as jnp
        from reference import control, kvclient, sim
        c = self.cfg["cluster"]
        model = sim.Model(self.cfg, write_rate=0.0, read_rate=0.0, phi=0.0,
                          key_cdf=np.arange(1, c["key_space"] + 1) /
                          c["key_space"], key_zipf=False,
                          fdt=fdt or jnp.float32)
        if fault == "frozen":
            model.tick = lambda st, key: (st, jnp.int32(0))
        seed = self.cell["cluster_seed"]
        ctl = control.Controller(model, seed)
        client = kvclient.Client(model, self.cell["timeout_ticks"])
        st, rng = model.init_state(), jax.random.PRNGKey(seed)
        floor, ref = 0, []
        for i, (kind, key, value) in enumerate(self.ops[:len(self.answers)]):
            if i == self.leased_after:
                role, alive, sec_of, obs_of = ctl.lease(
                    np.asarray(st["role"]), np.asarray(st["alive"]),
                    *self.cell["roles"])
                st = dict(st, role=jnp.asarray(role),
                          alive=jnp.asarray(alive),
                          sec_of=jnp.asarray(sec_of),
                          obs_of=jnp.asarray(obs_of))
            kid = kvclient.key_id(key, model.K)
            if kind == "put":
                st, rng, status, pos, lat = client.put(st, rng, kid, value)
                status, pos, lat = int(status), int(pos), int(lat)
                ans = ("ok", pos, 0, lat) if status == kvclient.OK else \
                    (FAILED, -1, 0, -1)
                if status == kvclient.OK:
                    floor = max(floor, pos + 1)
            else:
                st, rng, status, val, fence, lat = client.get(
                    st, rng, kid, floor)
                status = int(status)
                val = int(val) + (fault == "altered")
                ans = ("ok", int(fence), val, int(lat)) \
                    if status == kvclient.OK else (FAILED, -1, 0, -1)
                if status == kvclient.OK:
                    floor = max(floor, int(fence))
            ref.append(ans)
        return ref, jax.tree.map(np.asarray, st)

    def compare(self, answers, final, ref_answers, ref_state) -> list:
        """The checks of `answers` and `final` (the program's, as a rule)
        against the reference's."""
        from reference.kvclient import key_id
        mismatch = harness.int_mismatches(final, ref_state,
                                          harness.int_leaves(ref_state))
        floats = harness.worst(*(
            harness.rel_gap(final[k], ref_state[k])
            for k in ("cost_accrued", "read_lat_sum", "read_lat_max")))
        n_keys = self.cfg["cluster"]["key_space"]
        lim = self.cell["limits"]
        return [
            harness.Check("answer_mismatch",
                          sum(a != b for a, b in zip(answers, ref_answers)),
                          lim["answer_mismatch"]),
            harness.Check("state_mismatch", mismatch, lim["state_mismatch"]),
            harness.Check("stale_reads", stale_reads(
                self.ops, answers, lambda k: key_id(k, n_keys)),
                lim["stale_reads"]),
            harness.Check("price_gap", harness.rel_gap(
                final["spot_price"], ref_state["spot_price"]),
                lim["price_gap"]),
            harness.Check("cost_gap", floats, lim["cost_gap"])]


def stale_reads(ops, answers, key_id) -> int:
    """Gets that missed the last acknowledged put to their key, by a
    plain dictionary of the acknowledgements; a key with a put that
    failed (it may or may not have landed) is not judged after it."""
    last, unsure, bad = {}, set(), 0
    for (kind, key, value), ans in zip(ops, answers):
        kid = key_id(key)
        if kind == "put":
            if ans[0] == "ok":
                last[kid] = value
            else:
                unsure.add(kid)
        elif ans[0] == "ok" and kid not in unsure:
            bad += ans[2] != last.get(kid, 0)
    return bad
