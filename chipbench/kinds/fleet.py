"""Fleet cells: the coordinator, cluster and control loop of
``core/controlloop.py`` over a fleet configuration, driven from the seed.

The loop is closed: one ``ControlLoop.tick`` per event, each after the
heartbeats of every healthy node.  Set-up builds the coordinator (its
first fused plan table included), then fails ``backlog`` assigned nodes
and runs ``warmup_events`` events.  Events then alternate between the
traffic's kinds:

- ``fault``: a SEV1 report, kind drawn by the configuration's category
  shares, on an assigned healthy node drawn from the seed, made visible
  at the tick;
- ``repair``: one failed node, drawn from the seed, completes its repair
  at the tick and rejoins.

``replan_p95_ms`` is the 95th percentile of the ticks' host time in the
window.  After the window, a sample of the dispatched plans and of the
last plan table's totals, drawn from the seed, is compared with the
plain reference.
"""
from __future__ import annotations

import math
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import harness
from chipbench.reference import planner as ref_planner


class Fleet:
    """The program's control plane for one fleet configuration."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core.cluster import Cluster
        from repro.core.controlloop import ControlLoop
        from repro.core.coordinator import UnicronCoordinator
        from repro.core.costmodel import Hardware, TaskModel
        from repro.core.kvstore import KVStore
        from repro.core.waf import Task
        self.cfg, self.traffic = cfg, traffic
        models = cfg["models"]
        tasks = [Task(model=TaskModel(name=t["model"], seq_len=t["seq_len"],
                                      global_batch=t["global_batch"],
                                      **models[t["model"]]),
                      weight=t["weight"]) for t in cfg["tasks"]]
        self.gpn = cfg["gpus_per_node"]
        self.kv = KVStore()
        self.coord = UnicronCoordinator(
            tasks, list(cfg["assignment"]), Hardware(**cfg["hardware"]),
            kv=self.kv, mtbf_per_worker_s=cfg["mtbf_per_worker_s"],
            d_transition_s=cfg["d_transition_s"],
            plan_cache=None,
            n_cluster_workers=cfg["nodes"] * self.gpn,
            workers_per_node=self.gpn, plan_engine=cfg["plan_engine"],
            prebuild_scenarios=cfg["prebuild_scenarios"])
        self.cluster = Cluster(cfg["nodes"], gpus_per_node=self.gpn)
        self.cluster.assign(list(cfg["assignment"]))
        self.loop = ControlLoop(self.coord, self.cluster, {})
        self.rng = np.random.default_rng([seed, 11])
        cats = cfg["sev1_categories"]
        share = np.array([c["share"] for c in cats], np.float64)
        self.cat_p = share / share.sum()
        self.cats = cats
        self.t = 0.0
        self.n_events = 0
        self.records: List[Dict] = []

    def _beat(self) -> None:
        ids = np.array([n.node_id for n in self.cluster.nodes if n.healthy])
        self.kv.heartbeat_batch(ids, self.t,
                                ttl=self.traffic["heartbeat_ttl_s"])

    def fault(self) -> int:
        assigned = sorted(n for n, t in self.cluster.placement.items()
                          if t is not None)
        node = int(self.rng.choice(assigned))
        cat = self.cats[int(self.rng.choice(len(self.cats), p=self.cat_p))]
        kind = str(self.rng.choice(cat["kinds"]))
        self.kv.put(f"/errors/{node}/{self.t:.3f}", {
            "node": node, "kind": kind, "severity": 1, "method": "benchmark",
            "raised_at": self.t, "visible_at": self.t}, now=self.t)
        return node

    def repair(self) -> int:
        failed = sorted(n.node_id for n in self.cluster.nodes
                        if not n.healthy)
        node = int(self.rng.choice(failed))
        self.cluster.nodes[node].repair_done_at = self.t
        return node

    def event(self, spans: harness.Spans) -> Dict:
        """One event: heartbeats, then the event made visible, then one
        timed tick.  Returns what the comparison and the readers need."""
        kinds = self.traffic["events"]
        kind = kinds[self.n_events % len(kinds)]
        self.n_events += 1
        self.t += self.traffic["tick_s"]
        self._beat()
        owner = None
        if kind == "fault":
            node = self.fault()
            owner = self.cluster.placement[node]
        else:
            node = self.repair()
        assign = tuple(e.n_workers for e in self.coord.entries)
        ps = self.coord.plan_stats
        hits, rebuilds, rebuild_s = (ps.lookup_hits, ps.table_rebuilds,
                                     ps.table_rebuild_s)
        dispatches = ps.device_dispatches
        with spans("tick"):
            t0 = time.perf_counter()
            evs = self.loop.tick(self.t)
            tick_s = time.perf_counter() - t0
        plans = [e.plan for e in evs if e.plan is not None]
        rec = {"kind": kind, "node": node, "owner": owner, "assign": assign,
               "healthy": self.cluster.healthy_workers(),
               "hit": ps.lookup_hits > hits,
               "plan": tuple(plans[-1]) if len(plans) == 1 else None,
               "tick_s": tick_s, "dispatch_s": ps.last_dispatch_s,
               "rebuilds": ps.table_rebuilds - rebuilds,
               "rebuild_s": ps.table_rebuild_s - rebuild_s,
               "device_dispatches": ps.device_dispatches - dispatches}
        self.records.append(rec)
        return rec

    def start(self) -> None:
        self._beat()
        self.loop.tick(self.t)
        for _ in range(self.traffic.get("backlog", 0)):
            self.t += self.traffic["tick_s"]
            self._beat()
            self.fault()
            self.loop.tick(self.t)


def budget_of(rec: Dict, w: int):
    """(budget, faulted task) the dispatched plan answers: the plan-table
    scenario on a lookup hit, the healthy workers on a fresh solve."""
    n_now = sum(rec["assign"])
    faulted = rec["owner"] if rec["kind"] == "fault" else None
    if not rec["hit"]:
        return rec["healthy"], faulted
    return (n_now - w if rec["kind"] == "fault" else n_now + w), faulted


def compare(cfg: dict, records: List[Dict], totals: Dict[str, float],
            assign, seed: int, n_events: int, n_scen: int,
            fl=None) -> Dict[str, float]:
    """Worst gap of a sample of dispatched plans below the reference's
    optimum, and worst relative gap of a sample of the last plan table's
    totals from the reference's optima."""
    fl = fl or ref_planner.fleet(cfg)
    rng = np.random.default_rng([seed, 13])
    pick = rng.choice(len(records), size=min(n_events, len(records)),
                      replace=False)
    plan_gap = 0.0
    for i in sorted(int(j) for j in pick):
        rec = records[i]
        if rec["plan"] is None:
            return {"plan_gap": math.inf, "totals_gap": math.inf}
        budget, faulted = budget_of(rec, fl.w)
        m = len(rec["assign"])
        plan_gap = max(plan_gap, ref_planner.plan_gap(
            fl, list(range(m)), rec["assign"], budget, faulted, rec["plan"]))
    keys = sorted(totals)
    chosen = ["join:1"] + [keys[int(j)] for j in rng.choice(
        len(keys), size=min(n_scen, len(keys)), replace=False)]
    return {"plan_gap": plan_gap,
            "totals_gap": ref_planner.totals_gap(fl, totals, assign,
                                                 chosen)}


def run(ctx) -> None:
    cfg, traffic, cell = ctx.config, ctx.traffic, ctx.cell
    spans = harness.Spans(annotate=ctx.trace)
    fleet = Fleet(cfg, traffic, ctx.seed)
    fleet.start()
    for _ in range(traffic["warmup_events"]):
        fleet.event(spans)
    first = len(fleet.records)
    compiles0 = ctx.compiles.count
    ctx.tracer.start()
    t_window = time.perf_counter()
    seconds = ctx.seconds
    if ctx.trace:          # the traced window stays short: many small ops
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    with spans("window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            fleet.event(spans)
        t1 = time.perf_counter()
    ctx.tracer.stop()
    compiles = ctx.compiles.count - compiles0
    device = harness.device_info(ctx.devices, ctx.tracer.summary)
    recs = fleet.records[first:]
    ms = np.array([r["tick_s"] for r in recs]) * 1e3
    print(f"window: {len(recs)} events in {t1 - t0:.3f} s, tick ms p50 "
          f"{np.percentile(ms, 50):.3f} p95 {np.percentile(ms, 95):.3f} "
          f"max {ms.max():.3f}, rebuilds {sum(r['rebuilds'] for r in recs)}, "
          f"device dispatches {sum(r['device_dispatches'] for r in recs)}, "
          f"lookup hits {sum(r['hit'] for r in recs)}, compiles in window "
          f"{compiles}", flush=True)
    if ctx.tracer.summary is not None:
        # the profiler keeps a bounded number of device events: a window
        # that overflows it loses programs and overstates the idle share
        _, held = ctx.tracer.summary.modules(lambda n: n == "jit__program")
        print(f"trace: {held} plan programs held of "
              f"{sum(r['device_dispatches'] for r in recs)} dispatched",
              file=sys.stderr, flush=True)
    run_rec = harness.Run(
        cell=cell, config=cfg, traffic=traffic, spans=spans, window=(t0, t1),
        counters={"events": recs, "compiles_in_window": compiles},
        summary=ctx.tracer.summary, peak=ctx.peak)

    table = fleet.coord._table
    totals = table.rebuild_values()
    values = compare(cfg, recs, totals, table.assignment, ctx.seed,
                     traffic["check_events"], traffic["check_scenarios"])
    checks = [harness.Check(k, v, ctx.limits[k]) for k, v in values.items()]
    result = {"attempted": len(recs),
              "failed": sum(r["plan"] is None for r in recs),
              "device": device}
    if ctx.trace:
        result["metrics"] = harness.per_layer(ctx.bench, run_rec)
        result["breakdown"] = ctx.tracer.summary.breakdown()
    else:
        result["metrics"] = harness.end_to_end(ctx.bench, cell, {
            "setup_s": t_window - ctx.t_start,
            "replan_p95_ms": float(np.percentile(ms, 95))})
    harness.emit(result, checks)


def calibration_readings(cfg: dict, traffic: dict, seed: int,
                         seconds: float, variants: bool) -> Dict[str, Dict]:
    """A short window of the loop, then the program's numbers against the
    reference ("lower"); with ``variants``, the float32 control's and an
    altered answer's numbers, each put in the program's place."""
    spans = harness.Spans()
    fleet = Fleet(cfg, traffic, seed)
    fleet.start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        fleet.event(spans)
    recs = fleet.records
    table = fleet.coord._table
    totals = table.rebuild_values()
    n_ev, n_sc = traffic["check_events"], traffic["check_scenarios"]
    out = {"lower": dict(events=len(recs), **compare(
        cfg, recs, totals, table.assignment, seed, n_ev, n_sc))}
    if not variants:
        return out
    f32 = ref_planner.fleet(cfg, "float32")
    w = f32.w
    ctl_recs = []
    for rec in recs:
        budget, faulted = budget_of(rec, w)
        m = len(rec["assign"])
        _, plan = f32.optimum(f32.reward_rows(list(range(m)), rec["assign"],
                                              budget, faulted))
        ctl_recs.append(dict(rec, plan=tuple(plan)))
    ctl_totals = {k: f32.optimum(f32.reward_rows(
        *f32.scenario(k, table.assignment)))[0] for k in totals}
    out["control_f32"] = compare(cfg, ctl_recs, ctl_totals, table.assignment,
                                 seed, n_ev, n_sc)
    altered = []
    for rec in recs:
        plan = list(rec["plan"])
        big = int(np.argmax(plan))
        plan[big] -= w
        plan[(big + 1) % len(plan)] += w
        altered.append(dict(rec, plan=tuple(plan)))
    out["answer_altered"] = compare(cfg, altered, totals, table.assignment,
                                    seed, n_ev, n_sc)
    return out
