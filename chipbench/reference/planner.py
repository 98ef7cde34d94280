"""Plain reference of Unicron's reconfiguration plan (Eq. 2-5).

For a fleet configuration file: each task's achieved FLOP/s on x workers
is the best feasible (dp, tp, pp, micro-batch) layout of the analytic
cost model; its WAF is weight times that, zero below the fewest workers
any layout fits on; its reward for moving from x_old to x workers is
WAF(x) times the expected run duration, minus WAF(x_old) times the
transition time when the task changes or one of its workers faulted
(Eq. 3-4); and a plan is an assignment whose rewards sum to the most
over all assignments within the worker budget, found by a dynamic
program over the tasks (Eq. 5).  Nothing of the program under test is
imported.  ``dtype`` sets the precision of the rewards and the dynamic
program (``float64`` is the reference; ``float32`` the control).
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class Fleet:
    def __init__(self, cfg: dict, dtype: str = "float64"):
        self.cfg = cfg
        self.hw = cfg["hardware"]
        self.dtype = np.dtype(dtype)
        self.tasks = [dict(t, **cfg["models"][t["model"]])
                      for t in cfg["tasks"]]
        self.n_cluster = cfg["nodes"] * cfg["gpus_per_node"]
        self.w = cfg["gpus_per_node"]
        self.d_running = cfg["mtbf_per_worker_s"] / self.n_cluster
        self.d_transition = cfg["d_transition_s"]
        self.n_max = self.n_cluster + self.w
        self._flops: Dict[tuple, np.ndarray] = {}
        self._curves = [self._waf_curve(i) for i in range(len(self.tasks))]

    # ---- cost model --------------------------------------------------

    def _layouts(self, t: dict) -> List[Tuple[int, int, int]]:
        hw = self.hw
        out = []
        for tp in (1, 2, 4, 8, 16):
            if tp > min(self.n_max, hw["intra_size"]):
                continue
            pp = 1
            while tp * pp <= self.n_max and pp <= t["n_layers"]:
                if t["n_layers"] % pp == 0:
                    for mb in (1, 2, 4):
                        if self._memory(t, tp, pp, mb) <= hw["hbm_bytes"]:
                            out.append((tp, pp, mb))
                pp *= 2
        return out

    @staticmethod
    def _memory(t: dict, tp: int, pp: int, mb: int) -> float:
        shard = t["n_params"] / (tp * pp)
        act = (22.0 * t["seq_len"] * mb * t["d_model"]
               * (t["n_layers"] / pp) / tp) * min(pp, 4)
        return 16.0 * shard + act

    def _iter_time(self, t: dict, dp: int, tp: int, pp: int,
                   mb: int) -> float:
        hw = self.hw
        B, S, N = t["global_batch"], t["seq_len"], t["n_params"]
        L, d = t["n_layers"], t["d_model"]
        m = max(1, math.ceil(B / (dp * mb)))
        comp = 6.0 * N * B * S / (dp * tp * pp * hw["peak_flops"]
                                  * hw["compute_eff"])
        comp *= (m + pp - 1) / m
        t_tp = 0.0
        if tp > 1:
            bw = hw["intra_bw"] if tp <= hw["intra_size"] else hw["inter_bw"]
            t_tp = (4 * L / pp * (2.0 * S * mb * d) * m) * 2 * (tp - 1) \
                / tp / bw
        t_dp = 0.0
        if dp > 1:
            bw = (hw["intra_bw"] if dp * tp * pp <= hw["intra_size"]
                  else hw["inter_bw"])
            t_dp = 0.5 * (2.0 * N / (tp * pp)) * 2 * (dp - 1) / dp / bw
        return (comp + t_tp + t_dp) * (math.ceil(B / dp) / (B / dp))

    def achieved(self, t: dict, x: int) -> float:
        best = 0.0
        for tp, pp, mb in self._layouts(t):
            if tp * pp > x:
                continue
            dp = x // (tp * pp)
            if dp < 1 or dp > t["global_batch"] or mb * dp > t["global_batch"]:
                continue
            flops = (6.0 * t["n_params"] * t["global_batch"] * t["seq_len"]
                     / self._iter_time(t, dp, tp, pp, mb))
            best = max(best, flops)
        return best

    def _waf_curve(self, i: int) -> np.ndarray:
        t = self.tasks[i]
        key = (t["model"], t["global_batch"], t["seq_len"])
        if key not in self._flops:
            self._flops[key] = np.array(
                [self.achieved(t, x) for x in range(self.n_max + 1)])
        f = self._flops[key].copy()
        feasible = np.nonzero(f[1:] > 0)[0]
        floor = int(feasible[0]) + 1 if feasible.size else self.n_max + 1
        f[:floor] = 0.0
        return t["weight"] * f

    # ---- rewards and plans -------------------------------------------

    def reward_rows(self, tasks: Sequence[int], assign: Sequence[int],
                    budget: int, faulted: Optional[int]) -> np.ndarray:
        """G(t, x) for x = 0..budget, one row per task (Eq. 3-4)."""
        rows = []
        for i, x_old in zip(tasks, assign):
            F = self._curves[i][:budget + 1]
            g = F * self.d_running - self._curves[i][x_old] \
                * self.d_transition
            if i != faulted and x_old <= budget:
                g[x_old] = F[x_old] * self.d_running
            rows.append(g)
        return np.array(rows, dtype=self.dtype)

    def optimum(self, rows: np.ndarray) -> Tuple[float, List[int]]:
        """Best total and an assignment reaching it (Eq. 5)."""
        n = rows.shape[1] - 1
        S = np.zeros(n + 1, self.dtype)
        choice = []
        for g in rows:
            # cand[j, k] = S[j - k] + g[k]; -inf where k > j
            pad = np.concatenate([np.full(n, -np.inf, self.dtype), S])
            cand = np.lib.stride_tricks.sliding_window_view(
                pad, n + 1)[:, ::-1] + g[None, :]
            arg = np.argmax(cand, axis=1)
            S = cand[np.arange(n + 1), arg]
            choice.append(arg)
        j = int(np.argmax(S))
        total = float(S[j])
        plan = [0] * len(rows)
        for i in range(len(rows) - 1, -1, -1):
            plan[i] = int(choice[i][j])
            j -= plan[i]
        return total, plan

    @staticmethod
    def value(rows: np.ndarray, plan: Sequence[int]) -> float:
        if len(plan) != len(rows) or min(plan) < 0 \
                or sum(plan) > rows.shape[1] - 1:
            return -math.inf
        return float(sum(float(r[x]) for r, x in zip(rows, plan)))

    def scenario(self, key: str, assign: Sequence[int]
                 ) -> Tuple[List[int], List[int], int, Optional[int]]:
        """(tasks, their current workers, budget, faulted task) of a plan
        table scenario from the assignment the table was built at."""
        m, n_now = len(assign), sum(assign)
        kind, _, arg = key.partition(":")
        tasks = list(range(m))
        if kind == "fault":
            return tasks, list(assign), max(n_now - self.w, 0), int(arg)
        if kind == "join":
            return tasks, list(assign), n_now + self.w * int(arg), None
        if kind == "finish":
            i = int(arg)
            rest = tasks[:i] + tasks[i + 1:]
            return rest, [assign[j] for j in rest], n_now, None
        raise ValueError(f"unknown scenario {key!r}")


@lru_cache(maxsize=4)
def _cached(cfg_json: str, dtype: str) -> Fleet:
    import json
    return Fleet(json.loads(cfg_json), dtype)


def fleet(cfg: dict, dtype: str = "float64") -> Fleet:
    import json
    return _cached(json.dumps(cfg, sort_keys=True), dtype)


def plan_gap(fl: Fleet, tasks, assign, budget, faulted,
             plan: Sequence[int]) -> float:
    """How far the plan's total reward lies below the optimum, relative
    to the optimum's magnitude (0 for an optimal plan)."""
    rows = fl.reward_rows(tasks, assign, budget, faulted)
    best, _ = fl.optimum(rows)
    got = Fleet.value(rows.astype(np.float64), plan)
    return (best - got) / abs(best) if math.isfinite(got) else math.inf


def totals_gap(fl: Fleet, totals: Dict[str, float], assign,
               keys: Sequence[str]) -> float:
    worst = 0.0
    for key in keys:
        tasks, a, budget, faulted = fl.scenario(key, assign)
        best, _ = fl.optimum(fl.reward_rows(tasks, a, budget, faulted))
        got = totals.get(key)
        if got is None:
            return math.inf
        worst = max(worst, abs(got - best) / abs(best))
    return worst
