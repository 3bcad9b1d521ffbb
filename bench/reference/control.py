"""Plain reference of the host control plane of one cluster: the paper's
Algorithm 1 ("peek": how many secretaries and observers to lease), the
spot-offer score of its equation 2, the multiple-choice secretary rule
(Algorithm 2, "peak") that picks offers, and the wiring of followers to
secretaries and observers to followers.

Every random choice comes from the cluster's own numpy generator,
`default_rng(seed + 1)`, in the order the rules below draw it, so a
seed defines the decisions as it defines the trajectory.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np

FOLLOWER, CANDIDATE, LEADER, SECRETARY, OBSERVER, DEAD = range(6)


class Controller:
    def __init__(self, model, seed: int):
        self.m = model
        self.rng = np.random.default_rng(seed + 1)
        self.hazard = np.full(model.S, 0.02)       # EWMA revocation rate
        self.leased = np.zeros(model.S, np.int64)  # per-site lease census
        self.reads_prev = 0

    # ------------------------------------------------------ Algorithm 1
    def decide(self, digest) -> tuple:
        """(secretaries to add, observers to add) after an epoch."""
        m, c = self.m, self.m.cfg
        revoked = np.full(m.S, int(digest["killed"]) / max(m.S, 1))
        seen = np.maximum(self.leased, 1)
        self.hazard = (1 - 0.3) * self.hazard + 0.3 * (revoked / seen)
        f, varpi = c["secretary_fanout"], c["write_ratio_threshold"]
        rho = float(np.mean(np.asarray(digest["spot_price"])[:m.S]))
        theta = c["budget_per_period"]
        followers = [s["followers"] for s in m.sites]
        k_s = int(digest["n_secretaries"])
        k_o = int(digest["n_observers"])
        reads, writes = int(digest["reads_arrived"]), \
            int(digest["writes_arrived"])
        dk_s = sum((F + (f + 1) // 2) // f for F in followers) - k_s
        zeta = writes / max(reads + writes, 1)
        dk_o = 0
        if zeta <= varpi:
            growth = (reads - self.reads_prev) / max(self.reads_prev, 1)
            if growth > c["read_growth_deadband"]:
                dk_o = len(followers)
                dk_o = min(dk_o, int(min(rho * dk_o, theta) / rho))
            elif growth < -c["read_growth_deadband"]:
                dk_o = max(-k_o, -len(followers))
            theta = max(0.0, theta - rho * dk_o)
            dk_s = min(dk_s, int(theta / rho))
        else:
            dk_s = min(dk_s, int(theta / rho))
            theta = max(0.0, theta - rho * max(dk_s, 0))
            dk_o = min(len(followers), int(theta / rho))
        self.reads_prev = reads
        return max(max(dk_s, -k_s), 0), max(dk_o, 0)

    # ----------------------------------------------- leasing and wiring
    def pick(self, score: np.ndarray, k: int) -> List[int]:
        """Algorithm 2: split the stream at a Binomial(n, 1/2) point and
        recurse; one choice is the 1/e stopping rule."""
        picked: List[int] = []

        def one(lo, hi):
            n_seen = int((hi - lo + 1) / math.e)
            best, best_i = score[lo], lo
            for i in range(lo, lo + n_seen):
                if score[i] > best:
                    best, best_i = score[i], i
            for i in range(lo + n_seen, hi + 1):
                if score[i] > best:
                    picked.append(i)
                    return
            picked.append(best_i)

        def rec(k, lo, hi):
            if hi < lo or k <= 0:
                return
            if k == 1:
                one(lo, hi)
                return
            cut = int(self.rng.binomial(hi - lo + 1, 0.5))
            cut = min(max(cut, 1), hi - lo)
            rec(k // 2, lo, lo + cut - 1)
            rec(k - k // 2, lo + cut, hi)

        rec(k, 0, len(score) - 1)
        return list(dict.fromkeys(picked))[:k]

    def lease(self, role, alive, want_sec: int, want_obs: int):
        """Lease spot instances into dead slots and wire the roles.
        Returns (role, alive, sec_of, obs_of) as numpy arrays."""
        m = self.m
        role, alive = np.array(role), np.array(alive)
        site = m.site

        def lease_into(slot_mask, want):
            free = [int(i) for i in np.flatnonzero(slot_mask &
                                                   (role == DEAD))]
            if want <= 0 or not free:
                return []
            pool = min(len(free) * 4, 256)
            offer_site = self.rng.integers(0, m.S, pool)
            cpu = self.rng.uniform(1, 4, pool)
            mem = self.rng.uniform(1, 8, pool)
            price = np.array([m.sites[s]["spot_price_mean"]
                              for s in offer_site]) * \
                self.rng.uniform(0.6, 1.6, pool)
            score = (cpu + mem + 1.0 / np.maximum(price, 1e-6)) / \
                np.maximum(self.hazard[offer_site], 1e-3)
            slots: List[int] = []
            for i in self.pick(score.astype(float), min(want, len(free))):
                s = int(offer_site[i])
                cands = [f for f in free if site[f] == s and f not in slots]
                cands = cands or [f for f in free if f not in slots]
                if cands:
                    slots.append(cands[0])
                    self.leased[site[cands[0]]] += 1
            return slots

        for s in lease_into(m.is_sec_slot, want_sec):
            role[s], alive[s] = SECRETARY, True
        for s in lease_into(m.is_obs_slot, want_obs):
            role[s], alive[s] = OBSERVER, True

        N, V = m.N, m.V
        sec_of = np.full(N, -1, np.int32)
        obs_of = np.full(N, -1, np.int32)
        serving = [(role[i] in (FOLLOWER, LEADER)) and alive[i]
                   for i in range(N)]
        for s_id in range(m.S):
            secs = [i for i in range(N) if role[i] == SECRETARY and alive[i]
                    and site[i] == s_id]
            fols = [i for i in range(V) if serving[i] and site[i] == s_id]
            for j, f in enumerate(fols if secs else []):
                sec_of[f] = secs[j % len(secs)]
            obss = [i for i in range(N) if role[i] == OBSERVER and alive[i]
                    and site[i] == s_id]
            for j, o in enumerate(obss if fols else []):
                obs_of[o] = fols[j % len(fols)]
        everyone = [i for i in range(V) if serving[i]]
        for o in range(N):
            if role[o] == OBSERVER and alive[o] and obs_of[o] < 0 and \
                    everyone:
                obs_of[o] = everyone[o % len(everyone)]
        return role, alive, sec_of, obs_of
