"""CPU tests of the on-chip benchmark: the trace reduction on a recorded
trace, name resolution of every cell's files, the refusal to run
without a TPU, the FLOP count against XLA's, and ``correct`` coming out
false with the control or a planted fault at a size a test run holds.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import flops, harness, trace  # noqa: E402
from chipbench.reference import dense_lm  # noqa: E402

SAMPLE = os.path.join(HERE, "data", "sample.xplane.pb")
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512)


# ---------------------------------------------------------------------------
# the trace reduction, on a trace recorded on one v5e: three rounds of a
# bf16 matmul program, a 20 ms host sleep, a flash-attention kernel and an
# SSD-scan kernel, each inside a bench.* TraceAnnotation
# ---------------------------------------------------------------------------


def test_trace_busy_union_and_idle_by_span():
    s = trace.summarize(SAMPLE)
    tr = s.trace
    assert tr.devices == [0]
    assert [n for n, _, _ in tr.spans].count("bench.sleep") == 3
    ops = trace.clip(tr.ops[0], s.lo, s.hi)
    # the union is no more than the summed durations, no less than the
    # longest op, and equal to a brute-force sweep over the boundaries
    total = sum(e - b for _, b, e in ops)
    busy = trace.busy_ns(tr, 0, s.lo, s.hi)
    assert max(e - b for _, b, e in ops) <= busy <= total
    edges = sorted({b for _, b, _ in ops} | {e for _, _, e in ops})
    sweep = sum(hi - lo for lo, hi in zip(edges, edges[1:])
                if any(b <= lo and hi <= e for _, b, e in ops))
    assert busy == sweep
    gaps = trace.idle_gaps(tr, 0, s.lo, s.hi)
    assert busy + sum(e - b for b, e in gaps) == s.hi - s.lo
    by_span = trace.idle_by_span(tr, 0, s.lo, s.hi)
    assert max(by_span, key=by_span.get) == "bench.sleep"
    assert by_span["bench.sleep"] >= 3 * 19_000_000   # three 20 ms sleeps
    assert abs(s.busy_s - busy * 1e-9) < 1e-12


def test_trace_per_kernel_time():
    s = trace.summarize(SAMPLE)
    secs, n = s.ops(lambda name: "tpu_custom_call" in name)
    assert n == 6                          # 3 flash + 3 SSD kernel calls
    fa, n_fa = s.ops(lambda name: "tpu_custom_call" in name
                     and "bf16[1,4,512,128]" in name
                     and "bf16[1,2,512,128]" in name)
    assert n_fa == 3 and 0 < fa < secs
    mods, n_mod = s.modules(lambda name: name == "jit_fa")
    assert n_mod == 3 and mods >= fa
    top = dict(s.breakdown()["device_ops"])
    assert trace.op_name("%fa.1 = bf16[1] custom-call()") == "fa.1"
    assert top["fa.1"] == pytest.approx(fa)


# ---------------------------------------------------------------------------
# every name in BENCHMARK.json resolves to its own file
# ---------------------------------------------------------------------------


def test_every_name_resolves_to_its_file():
    bench = harness.load_bench()
    names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert c["file"].startswith(bench["paths"][0] + "/")
    used = set()
    for w in bench["workloads"]:
        cell, cfg, traffic = harness.resolve(bench, w["name"])
        used.add(w["config"])
        assert os.path.exists(os.path.join(
            harness.HERE, "kinds", traffic["kind"] + ".py"))
        if traffic["kind"] == "train":
            model = harness.load_module("models", cfg["model_type"])
            assert all(callable(getattr(model, f)) for f in (
                "arch_config", "params", "seq_loss", "train_flops"))
        limits = harness.load_json(os.path.join(
            harness.HERE, "limits", w["name"] + ".json"))
        assert limits and all(v > 0 for v in limits.values())
    assert used == names
    empty = harness.Run(cell={"name": "x"}, config={}, traffic={},
                        spans=harness.Spans(), window=(0.0, 1.0),
                        counters={})
    for m in bench["per_layer"]:
        assert os.path.exists(harness.metric_path(m["name"])), m["name"]
        # a reader that finds nothing to read returns nothing
        assert harness.load_reader(m["name"])(empty) is None
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "qwen3-4b.faults", "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


# ---------------------------------------------------------------------------
# FLOPs from shapes against XLA's own count
# ---------------------------------------------------------------------------


def test_forward_flops_match_cost_analysis():
    cfg = dict(harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", "qwen3-4b-1chip.json")))
    cfg.update(hidden_size=256, intermediate_size=1024,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               num_hidden_layers=2, vocab_size=1024, torch_dtype="float32")
    S = 256
    qwen3 = harness.load_module("models", cfg["model_type"])
    params = qwen3.params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((S,), jnp.int32)
    # one query block: the plain reference computes every score, masked
    fwd = jax.jit(lambda p, t: dense_lm.seq_loss(p, t, cfg, block=S))
    cost = fwd.lower(params, toks).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = flops.dense_forward_flops(cfg, S, 1, causal=False)
    # matmuls are all but a few percent of the program (norms, softmax,
    # rotary and the loss are the rest)
    assert ours <= cost["flops"] <= 1.08 * ours


def test_train_flops_of_the_cell():
    cfg = harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", "qwen3-4b-1chip.json"))
    # 6 N T over the 0.299 B multiplied weights plus causal attention
    n = flops.dense_matmul_params(cfg)
    assert n == 2 * (2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728) \
        + 37984 * 2560
    got = harness.load_module("models", cfg["model_type"]).train_flops(
        cfg, 4096, 4)
    attn = 3 * 2 * 2 * 4096 ** 2 * 32 * 128 * 2 * 4 / 2
    assert got == pytest.approx(6 * n * 4 * 4096 + attn)


# ---------------------------------------------------------------------------
# correct comes out false: the control, and each fault a cell can have
# ---------------------------------------------------------------------------


def _tiny_train_ctx(workload: str, seconds: float = 2.0):
    bench = harness.load_bench()
    cell, cfg, traffic = harness.resolve(bench, workload)
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    cfg["job"].update(seq_len=64, kernel="jnp")
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            workload + ".json"))
    return harness.Context(
        bench=bench, cell=cell, config=cfg, traffic=traffic,
        seed=2 ** 33 + 17, seconds=seconds, trace=False,
        t_start=time.perf_counter(), devices=jax.devices(), limits=limits,
        compiles=harness.CompileCounter(), tracer=harness.Tracer(False, "t"))


def _result(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_training_control_fails_a_limit():
    """The reference with its products' operands in float8 (one step
    below the configuration's bfloat16), in the program's place, fails
    at least one of the cell's limits."""
    ctx = _tiny_train_ctx("qwen3-4b.steady")
    from chipbench.kinds import train
    ref = train.reference(ctx.config, ctx.seed)
    ctl = train.reference(ctx.config, ctx.seed, dtype="float8_e4m3fn")
    vals = dense_lm.compare(ctl, ref)
    assert any(vals[k] > ctx.limits[k] for k in ctx.limits), vals


@pytest.mark.parametrize("config,tiny", [("qwen3-4b-1chip", TINY)])
def test_reference_is_the_program_in_float32(config, tiny):
    """In float32 the set-up's three steps through the program and the
    plain reference agree to rounding: the reference computes the same
    model and optimizer (both in bfloat16 are for the chip's limits)."""
    from chipbench.kinds import train
    cfg = copy.deepcopy(harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", config + ".json")))
    cfg.update(tiny, torch_dtype="float32")
    cfg["job"].update(seq_len=64, kernel="jnp")
    traffic = harness.load_json(os.path.join(harness.HERE, "traffic",
                                             "steady.json"))
    seed = 2 ** 33 + 29
    job, state = train.build(cfg, traffic, seed)
    state, _, prog = train.warm(job, state, cfg, traffic, seed,
                                harness.Spans())
    train.free(job, state)
    gaps = dense_lm.compare(prog, train.reference(cfg, seed))
    assert max(gaps.values()) < 1e-5, gaps


def _plant(monkeypatch, fault):
    import repro.launch.train as lt
    real_step = lt.make_train_step

    if fault == "state_unchanged":
        def make(model, opt, n_micro, **kw):
            step = real_step(model, opt, n_micro, **kw)

            def broken(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return broken
        monkeypatch.setattr(lt, "make_train_step", make)
    elif fault == "half_batch":
        def make(model, opt, n_micro, **kw):
            step = real_step(model, opt, n_micro // 2, **kw)
            return lambda state, batch: step(
                state, jax.tree.map(lambda a: a[:n_micro // 2], batch))
        monkeypatch.setattr(lt, "make_train_step", make)
    elif fault == "exchange_left_out":
        real = lt.run_iteration_with_failure

        def no_exchange(grad_fn, params, microbatch_of, n_ranks, n_micro,
                        fail_rank=None, fail_after_mb=0):
            # each rank's accumulator stays its own: the sum holds the
            # survivor's own micro-batches only
            total, count = real(grad_fn, params, microbatch_of, n_ranks,
                                n_micro // n_ranks, fail_rank=None)
            return total, n_micro // n_ranks
        monkeypatch.setattr(lt, "run_iteration_with_failure", no_exchange)


@pytest.mark.parametrize("workload,fault", [
    ("qwen3-4b.steady", None),
    ("qwen3-4b.steady", "state_unchanged"),
    ("qwen3-4b.steady", "half_batch"),
    ("qwen3-4b.faults", None),
    ("qwen3-4b.faults", "state_unchanged"),
    ("qwen3-4b.faults", "half_batch"),
    ("qwen3-4b.faults", "exchange_left_out"),
])
def test_training_fault_makes_correct_false(workload, fault, monkeypatch,
                                            capsys):
    """A whole run at a tiny size: sound, it is correct; with the timed
    path broken underneath, it is not."""
    from chipbench.kinds import train
    _plant(monkeypatch, fault)
    train.run(_tiny_train_ctx(workload, seconds=1.0))
    res = _result(capsys)
    assert res["correct"] is (fault is None), res["checks"]


def _small_fleet():
    bench = harness.load_bench()
    cell, cfg, traffic = harness.resolve(bench, "fleet-1024x32.replan")
    cfg = copy.deepcopy(cfg)
    cfg.update(nodes=16, tasks=cfg["tasks"][:4], assignment=[32] * 4)
    traffic = dict(traffic, warmup_events=2, check_events=6,
                   check_scenarios=4)
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            "fleet-1024x32.replan.json"))
    return bench, cell, cfg, traffic, limits


def test_fleet_control_fails_a_limit():
    """The reference's dynamic program in float32 (one step below the
    configuration's float64), in the program's place."""
    from chipbench.kinds import fleet
    _, _, cfg, traffic, limits = _small_fleet()
    vals = fleet.calibration_readings(cfg, traffic, 2 ** 33 + 3, 1.0,
                                      variants=True)
    ctl = vals["control_f32"]
    assert any(ctl[k] > limits[k] for k in limits), ctl
    assert all(vals["lower"][k] <= limits[k] for k in limits), vals


def test_fleet_altered_answer_makes_correct_false(monkeypatch, capsys):
    from chipbench.kinds import fleet
    from repro.core import coordinator
    from repro.core.planner import Plan
    real = coordinator.UnicronCoordinator.reconfigure

    def altered(self, *a, **kw):
        plan = real(self, *a, **kw)
        x = list(plan.assignment)
        big = int(np.argmax(x))
        x[big] -= 8
        x[(big + 1) % len(x)] += 8
        return Plan(tuple(x), plan.total_reward, plan.waf)
    monkeypatch.setattr(coordinator.UnicronCoordinator, "reconfigure",
                        altered)
    bench, cell, cfg, traffic, limits = _small_fleet()
    ctx = harness.Context(
        bench=bench, cell=cell, config=cfg, traffic=traffic,
        seed=2 ** 33 + 5, seconds=1.0, trace=False,
        t_start=time.perf_counter(), devices=jax.devices(), limits=limits,
        compiles=harness.CompileCounter(), tracer=harness.Tracer(False, "t"))
    fleet.run(ctx)
    assert _result(capsys)["correct"] is False
