"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = ';'-separated
key=value pairs); ``--json PATH`` additionally writes the same rows as
structured JSON (``[{"name", "us_per_call", "derived": {...}}, ...]``)
so the perf trajectory can be tracked across PRs.  Everything is
laptop-scaled but structurally faithful to the paper's experiments; the
full-size parameters live in ``repro.configs.paper_workloads`` and run
unchanged on a pod.

    PYTHONPATH=src python -m benchmarks.run [--only fig4 ...] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.algorithms import (MSParams, RMATParams, UTSParams,
                              bc_single_node, bc_spec, ms_spec,
                              naive_render, rmat_graph, uts_sequential,
                              uts_spec)
from repro.core import (AutoscalePolicy, ProviderModel, StagedController,
                        TaskShape, VMPrice, characterize,
                        emr_cluster_cost, make_pool, price_performance,
                        run_irregular, serverless_cost, vm_cost)
from repro.core.adaptive import Stage as CtrlStage
from repro.configs.paper_workloads import (BC_SCALED, BC_SCALED_TASKS,
                                           MS_SCALED, UTS_SCALED)
from repro.kernels.dispatch import enable_compile_cache

ROWS = []
JSON_ROWS = []


def _jsonable(v):
    """numpy scalars/bools -> native Python so json.dump round-trips."""
    if isinstance(v, (np.integer, np.floating, np.bool_)):
        return v.item()
    return v


def emit(name: str, us_per_call: float, **derived) -> None:
    kv = ";".join(f"{k}={v}" for k, v in derived.items())
    row = f"{name},{us_per_call:.1f},{kv}"
    ROWS.append(row)
    JSON_ROWS.append({
        "name": name,
        "us_per_call": round(float(us_per_call), 1),
        "derived": {k: _jsonable(v) for k, v in derived.items()},
    })
    print(row, flush=True)


# -- Table 1: UTS tree sizes ---------------------------------------------------

def table1_uts_tree_sizes() -> None:
    """Tree size vs depth (seed 19, b0=4): exponential growth law."""
    sizes = {}
    t0 = time.monotonic()
    for d in range(4, 11):
        sizes[d] = uts_sequential(UTSParams(seed=19, b0=4.0, max_depth=d,
                                            chunk=4096))
    wall = time.monotonic() - t0
    growth = [sizes[d + 1] / sizes[d] for d in range(4, 10)]
    emit("table1_uts_tree_sizes", wall / 7 * 1e6,
         **{f"d{d}": n for d, n in sizes.items()},
         mean_growth=round(float(np.mean(growth)), 2))


# -- Table 2: algorithm characterization ----------------------------------------

def table2_characterization() -> None:
    """C_L per algorithm (paper: UTS 1.20, MS 4.06, BC 0.23).

    Each workload runs twice with a fresh executor: the first pass warms
    jit caches (compile time would otherwise swamp the duration CDF —
    the single-core stand-in for warm FaaS containers, §5)."""
    t0 = time.monotonic()
    cvs = {}

    def measured(spec, **kw):
        with make_pool("local", max_concurrency=1,
                       invoke_overhead=0.0) as warm:
            run_irregular(warm, spec, **kw)             # warm jit caches
        with make_pool("local", max_concurrency=1,
                       invoke_overhead=0.0) as ex:
            run_irregular(ex, spec, **kw)
            return characterize(ex.records).cv

    cvs["uts"] = measured(
        uts_spec(UTSParams(seed=19, b0=4.0, max_depth=9, chunk=128)),
        shape=TaskShape(6, 300))
    cvs["ms"] = measured(ms_spec(MS_SCALED))
    cvs["bc"] = measured(bc_spec(BC_SCALED, n_tasks=BC_SCALED_TASKS))
    wall = time.monotonic() - t0
    emit("table2_characterization", wall * 1e6,
         cv_uts=round(cvs["uts"], 3), cv_ms=round(cvs["ms"], 3),
         cv_bc=round(cvs["bc"], 3),
         paper_cv_uts=1.20, paper_cv_ms=4.06, paper_cv_bc=0.23,
         paper_ordering_ms_gt_uts_gt_bc=(cvs["ms"] > cvs["uts"]
                                         > cvs["bc"]))


# -- Table 4: invocation overheads -----------------------------------------------

def table4_invocation_overheads() -> None:
    """Avg overhead: elastic (FaaS-modelled) vs local thread."""
    n = 200
    with make_pool("elastic", max_concurrency=1, invoke_overhead=13e-3,
                   invoke_rate_limit=None) as ex:
        ex.submit(lambda: None).result()  # warm
        t0 = time.monotonic()
        for _ in range(20):
            ex.submit(lambda: None).result()
        remote_us = (time.monotonic() - t0) / 20 * 1e6
    with make_pool("local", max_concurrency=1,
                   invoke_overhead=18e-6) as ex:
        ex.submit(lambda: None).result()
        t0 = time.monotonic()
        for _ in range(n):
            ex.submit(lambda: None).result()
        local_us = (time.monotonic() - t0) / n * 1e6
    emit("table4_invocation_overheads", remote_us,
         remote_us=round(remote_us, 1), local_us=round(local_us, 1),
         ratio=round(remote_us / max(local_us, 1e-9), 1),
         paper_remote_ms=13, paper_local_us=18)


# -- Table 5: UTS performance / parallel efficiency ------------------------------

def table5_uts_performance() -> None:
    p = UTSParams(seed=19, b0=4.0, max_depth=9, chunk=2048)
    t0 = time.monotonic()
    total = uts_sequential(p)
    t_seq = time.monotonic() - t0
    results = {"sequential": (t_seq, 1)}
    for name, width in (("pool4", 4), ("pool8", 8)):
        with make_pool("elastic", max_concurrency=width,
                       invoke_overhead=0.0005,
                       invoke_rate_limit=None) as ex:
            t0 = time.monotonic()
            r = run_irregular(ex, uts_spec(p), shape=TaskShape(8, 4000))
            results[name] = (time.monotonic() - t0, width)
            assert r.output == total
    seq_tput = total / results["sequential"][0]
    derived = {"nodes": total,
               "seq_Mnodes_s": round(seq_tput / 1e6, 2)}
    for name, (t, w) in results.items():
        if name == "sequential":
            continue
        tput = total / t
        derived[f"{name}_Mnodes_s"] = round(tput / 1e6, 2)
        derived[f"{name}_parallel_eff"] = round(tput / (seq_tput * w), 3)
    emit("table5_uts_performance", results["pool8"][0] * 1e6, **derived)


# -- Fig 4: dynamic parameter optimization ---------------------------------------

def _scaled_controller() -> StagedController:
    # Listing 5 thresholds rescaled to a 16-worker pool
    return StagedController(
        initial=TaskShape(32, 500),
        stages=[
            CtrlStage(8, "above", TaskShape(8, 4000)),
            CtrlStage(13, "above", TaskShape(2, 8000)),
            CtrlStage(11, "below", TaskShape(2, 4000)),
            CtrlStage(2, "below", TaskShape(2, 1500)),
        ])


def fig4_dynamic_optimization() -> None:
    p = UTSParams(seed=19, b0=4.0, max_depth=10, chunk=2048)

    def run_static():
        with make_pool("elastic", max_concurrency=16,
                       invoke_overhead=0.001,
                       invoke_rate_limit=None) as ex:
            t0 = time.monotonic()
            r = run_irregular(ex, uts_spec(p), shape=TaskShape(4, 1000))
            return time.monotonic() - t0, r

    def run_dyn():
        with make_pool("elastic", max_concurrency=16,
                       invoke_overhead=0.001,
                       invoke_rate_limit=None) as ex:
            t0 = time.monotonic()
            r = run_irregular(ex, uts_spec(p), shape=TaskShape(32, 500),
                              controller=_scaled_controller())
            return time.monotonic() - t0, r

    run_static()  # warm jit caches
    statics = [run_static() for _ in range(3)]
    dyns = [run_dyn() for _ in range(3)]
    t_static = sorted(t for t, _ in statics)[1]      # median of 3
    t_dyn = sorted(t for t, _ in dyns)[1]
    r_static, r_dyn = statics[0][1], dyns[0][1]
    assert r_static.output == r_dyn.output
    emit("fig4_dynamic_optimization", t_dyn * 1e6,
         t_static_s=round(t_static, 3), t_dynamic_s=round(t_dyn, 3),
         improvement_pct=round(100 * (1 - t_dyn / t_static), 1),
         peak_concurrency=r_dyn.peak_concurrency,
         paper_improvement_pct=41.56)


def fig4_dynamic_optimization_sim() -> None:
    """Fig 4 at the paper's true scale (2000 workers, 13 ms invoke)
    under the virtual-time pool simulator — one core cannot exhibit
    concurrency effects, so the scheduling policy is isolated instead
    (core.simpool; the tree is actually traversed, time is simulated)."""
    from repro.core.simpool import simulate_uts_pool
    p = UTSParams(seed=19, b0=4.0, max_depth=11, chunk=4096)
    alpha = 10e-6  # s/node: a ~2500-node task ~ 38ms incl. overhead
    # static baseline = the best static (split, iters) from a grid sweep
    # (the paper tunes both versions for best performance)
    static = simulate_uts_pool(p, workers=2000, overhead_s=13e-3,
                               alpha_s_per_node=alpha,
                               shape=TaskShape(50, 5_000))
    ctrl = StagedController(initial=TaskShape(200, 2_000), stages=[
        CtrlStage(800, "above", TaskShape(50, 10_000)),
        CtrlStage(1300, "above", TaskShape(5, 25_000)),
        CtrlStage(1100, "below", TaskShape(5, 10_000)),
        CtrlStage(100, "below", TaskShape(5, 4_000)),
    ])
    dyn = simulate_uts_pool(p, workers=2000, overhead_s=13e-3,
                            alpha_s_per_node=alpha,
                            shape=TaskShape(200, 2_000),
                            controller=ctrl)
    assert static.count == dyn.count
    emit("fig4_dynamic_optimization_sim", dyn.virtual_time_s * 1e6,
         nodes=static.count,
         vtime_static_s=round(static.virtual_time_s, 3),
         vtime_dynamic_s=round(dyn.virtual_time_s, 3),
         improvement_pct=round(
             100 * (1 - dyn.virtual_time_s / static.virtual_time_s), 1),
         peak_static=static.peak_concurrency,
         peak_dynamic=dyn.peak_concurrency,
         paper_improvement_pct=41.56)


# -- Fig 5 / Table 6: Mariani-Silver executors + cost ----------------------------

def fig5_table6_mariani_silver() -> None:
    p = MS_SCALED
    runs = {}
    pools = (("parallel", "local",
              dict(max_concurrency=2, invoke_overhead=0.0)),
             ("serverless", "elastic",
              dict(max_concurrency=16, invoke_overhead=0.002,
                   invoke_rate_limit=None)),
             ("hybrid", "hybrid",
              dict(local_concurrency=2, elastic_concurrency=16)))
    for name, kind, cfg in pools:
        with make_pool(kind, **cfg) as pool:
            t0 = time.monotonic()
            run_irregular(pool, ms_spec(p))
            recs = None if kind == "local" else pool.records
            runs[name] = (time.monotonic() - t0, recs)

    mp = p.width * p.height / 1e6
    derived = {}
    for name, (wall, recs) in runs.items():
        if recs is None:
            cost = vm_cost(wall, VMPrice.named("c5.12xlarge"))
        else:
            cost = serverless_cost(recs, wall_time_s=wall)
        derived[f"{name}_s"] = round(wall, 3)
        derived[f"{name}_usd"] = round(cost.total, 6)
        derived[f"{name}_MPs_per_usd"] = round(
            price_performance(mp / wall, cost), 2)
    emit("fig5_table6_mariani_silver", runs["serverless"][0] * 1e6,
         **derived)


# -- Fig 6: BC scaling ------------------------------------------------------------

def fig6_bc_scaling() -> None:
    p = BC_SCALED
    adj = rmat_graph(p)
    expected = bc_single_node(adj, n_tasks=1)
    derived = {}
    wall8 = 0.0
    for width in (2, 4, 8):
        with make_pool("elastic", max_concurrency=width,
                       invoke_overhead=0.001,
                       invoke_rate_limit=None) as ex:
            t0 = time.monotonic()
            res = run_irregular(ex, bc_spec(p, n_tasks=BC_SCALED_TASKS,
                                            regenerate_graph=True))
            wall = time.monotonic() - t0
        assert np.allclose(res.output, expected, rtol=1e-4,
                           atol=1e-3)
        derived[f"w{width}_s"] = round(wall, 3)
        if width == 8:
            wall8 = wall
    emit("fig6_bc_scaling", wall8 * 1e6, n_vertices=p.n_vertices,
         tasks=BC_SCALED_TASKS, **derived)


# -- Figs 7-9: cost-performance --------------------------------------------------

def fig7_9_cost_performance() -> None:
    p = UTS_SCALED
    # serverless (static)
    with make_pool("elastic", max_concurrency=16, invoke_overhead=0.001,
                   invoke_rate_limit=None) as ex:
        t0 = time.monotonic()
        r_st = run_irregular(ex, uts_spec(p), shape=TaskShape(4, 1000))
        wall_st = time.monotonic() - t0
        cost_st = serverless_cost(ex.records, wall_time_s=wall_st)
    # serverless (dynamic, Listing 5 scaled)
    with make_pool("elastic", max_concurrency=16, invoke_overhead=0.001,
                   invoke_rate_limit=None) as ex:
        t0 = time.monotonic()
        r_dy = run_irregular(ex, uts_spec(p), shape=TaskShape(32, 500),
                             controller=_scaled_controller())
        wall_dy = time.monotonic() - t0
        cost_dy = serverless_cost(ex.records, wall_time_s=wall_dy)
    # "VM" (narrow local pool) and EMR-style cluster pricing on its time
    with make_pool("local", max_concurrency=2, invoke_overhead=0.0) as ex:
        t0 = time.monotonic()
        r_vm = run_irregular(ex, uts_spec(p), shape=TaskShape(4, 4000))
        wall_vm = time.monotonic() - t0
    cost_vm = vm_cost(wall_vm, VMPrice.named("c5.24xlarge"))
    cost_emr = emr_cluster_cost(wall_vm, workers=2)

    assert r_st.output == r_dy.output == r_vm.output
    nodes = r_st.output
    emit("fig7_9_cost_performance", wall_dy * 1e6,
         nodes=nodes,
         serverless_static_s=round(wall_st, 3),
         serverless_dynamic_s=round(wall_dy, 3),
         vm_s=round(wall_vm, 3),
         dyn_vs_static_time_pct=round(100 * (1 - wall_dy / wall_st), 1),
         dyn_extra_cost_pct=round(
             100 * (cost_dy.total / max(cost_st.total, 1e-12) - 1), 2),
         ppr_static=round(price_performance(nodes / wall_st / 1e6,
                                            cost_st), 0),
         ppr_dynamic=round(price_performance(nodes / wall_dy / 1e6,
                                             cost_dy), 0),
         ppr_vm=round(price_performance(nodes / wall_vm / 1e6,
                                        cost_vm), 0),
         ppr_emr=round(price_performance(nodes / wall_vm / 1e6,
                                         cost_emr), 0))


# -- Cost-performance at paper scale (2000 workers, provider dynamics) -----------

def cost_performance_sim() -> None:
    """Paper §4.3 ordering at true scale: elastic serverless UTS vs a
    static VM on price-performance (Eq. 7), under the virtual-time pool
    with the full provider model — 2 000 workers, 13 ms warm overhead,
    cold starts enabled, frontier-driven autoscale.  ``alpha``
    calibrates the laptop-size tree to paper-scale work (each node
    models ~4 ms of traversal), so task bodies dwarf invocation
    overhead exactly as the paper's §5.2 tuning ensures."""
    p = UTSParams(seed=19, b0=4.0, max_depth=10, chunk=4096)
    alpha = 4e-3
    dur = (lambda task, result: alpha * result[0])
    shape = TaskShape(100, 400)

    # elastic serverless: cold starts on, capacity follows the frontier
    with make_pool("sim", max_concurrency=2000,
                   provider=ProviderModel.aws_lambda(),
                   duration_fn=dur) as pool:
        r_sls = run_irregular(pool, uts_spec(p), shape=shape,
                              autoscale=AutoscalePolicy(min_capacity=8,
                                                        max_capacity=2000))
    # static VM: c5.24xlarge (96 vCPU), billed for the whole makespan
    with make_pool("sim", max_concurrency=96,
                   provider=ProviderModel.local_vm(),
                   duration_fn=dur) as pool:
        r_vm = run_irregular(pool, uts_spec(p), shape=shape)
    assert r_sls.output == r_vm.output
    nodes = r_sls.output
    cost_vm = vm_cost(r_vm.makespan_s, VMPrice.named("c5.24xlarge"))
    cost_emr = emr_cluster_cost(r_vm.makespan_s, workers=1)
    ppr_sls = price_performance(nodes / r_sls.makespan_s / 1e6, r_sls.cost)
    ppr_vm = price_performance(nodes / r_vm.makespan_s / 1e6, cost_vm)
    ppr_emr = price_performance(nodes / r_vm.makespan_s / 1e6, cost_emr)
    emit("cost_performance_sim", r_sls.makespan_s * 1e6,
         nodes=nodes,
         serverless_vt_s=round(r_sls.makespan_s, 3),
         vm_vt_s=round(r_vm.makespan_s, 3),
         serverless_usd=round(r_sls.cost.total, 6),
         vm_usd=round(cost_vm.total, 6),
         serverless_peak=r_sls.peak_concurrency,
         serverless_cold_starts=r_sls.cold_starts,
         autoscale_resizes=len(r_sls.autoscale_decisions),
         ppr_serverless=round(ppr_sls, 3),
         ppr_vm=round(ppr_vm, 3),
         ppr_emr=round(ppr_emr, 3),
         serverless_beats_vm=ppr_sls > ppr_vm,
         equal_cost_speedup=round(ppr_sls / ppr_vm, 2))


def cold_warm_ablation() -> None:
    """Cold-start tax from actual runs: the same UTS drive under the
    same provider model with provisioning latency on (500 ms cold
    start, containers reused within the keep-alive window) vs the
    paper's prewarmed-container assumption.  Both makespan and invoice
    come live from the run's event timeline."""
    p = UTSParams(seed=19, b0=4.0, max_depth=9, chunk=4096)
    alpha = 16e-3
    dur = (lambda task, result: alpha * result[0])
    shape = TaskShape(50, 100)
    runs = {}
    for label, prov in (
            ("cold", ProviderModel.aws_lambda(cold_start_s=0.5)),
            ("warm", ProviderModel.prewarmed())):
        with make_pool("sim", max_concurrency=2000, provider=prov,
                       duration_fn=dur) as pool:
            runs[label] = run_irregular(pool, uts_spec(p), shape=shape)
    cold, warm = runs["cold"], runs["warm"]
    assert cold.output == warm.output
    emit("cold_warm_ablation", cold.makespan_s * 1e6,
         nodes=cold.output, tasks=cold.tasks,
         cold_vt_s=round(cold.makespan_s, 3),
         warm_vt_s=round(warm.makespan_s, 3),
         cold_penalty_pct=round(
             100 * (cold.makespan_s / warm.makespan_s - 1), 1),
         cold_usd=round(cold.cost.total, 6),
         warm_usd=round(warm.cost.total, 6),
         cost_penalty_pct=round(
             100 * (cold.cost.total / warm.cost.total - 1), 1),
         containers_provisioned=cold.cold_starts,
         penalty_measurable=cold.makespan_s > warm.makespan_s)


# -- PR5: record -> analyze -> calibrate -> replay (repro.trace) -----------------

def trace_record_replay() -> None:
    """The trace subsystem, end to end, at 100k+ events.

    A paper-scale UTS run on the provider-modelled sim pool records
    through the spill-backed ``TraceStore`` (bounded resident memory:
    only the ring stays in RAM, everything streams to JSONL);
    ``render_concurrency_figure`` emits the Fig. 4 concurrency +
    capacity-staircase artifacts straight from the trace; the recorded
    workload is then replayed — same provider (fidelity check), a
    GCF-like platform, and an EWMA-autoscaled pool (what-if rows) —
    and ``fit_provider`` recovers a known preset from a synthetic
    saturating trace."""
    from repro.trace import (TraceStore, calibrate, extract_workload,
                             render_concurrency_figure, replay)

    p = UTSParams(seed=19, b0=4.0, max_depth=9, chunk=2048)
    prov = ProviderModel.aws_lambda()
    store = TraceStore(ring_size=4096)  # spills to a temp JSONL
    with make_pool("sim", max_concurrency=512, provider=prov,
                   trace=store) as pool:
        rec = run_irregular(pool, uts_spec(p), shape=TaskShape(32, 16))
    events_total = len(store)
    resident = store.resident_events

    # what-if replays over one extraction (no algorithm re-run)
    wl = extract_workload(store, provider=prov)
    ewma_trace = TraceStore(ring_size=4096)
    r_same = replay(wl, provider=prov, max_concurrency=512)
    r_gcf = replay(wl, provider=ProviderModel.gcf(),
                   max_concurrency=512)
    r_ewma = replay(wl, provider=prov, max_concurrency=512,
                    autoscale=AutoscalePolicy(
                        min_capacity=32, max_capacity=512,
                        ewma_alpha=0.5, grow_cooldown_s=0.05,
                        shrink_cooldown_s=0.05),
                    trace=ewma_trace)
    parity_pct = 100 * abs(r_same.makespan_s - rec.makespan_s) \
        / rec.makespan_s

    # Fig. 4 artifacts straight from the traces (PNG when matplotlib
    # is importable; CSV + ASCII always)
    out_base = os.path.join(os.path.dirname(__file__), "..", "results",
                            "trace", "fig4_pr5")
    arts = render_concurrency_figure(
        {"recorded": store, "replay-ewma": ewma_trace}, out_base)
    store.close()
    ewma_trace.close()

    # calibration: recover a known preset from its own synthetic trace
    true = ProviderModel.aws_lambda(
        cold_start_s=0.4, warm_overhead_s=0.02, burst_concurrency=5,
        scaling_ramp_per_min=120.0)
    with make_pool("sim", max_concurrency=1000, provider=true) as cp:
        for f in [cp.submit(lambda: 0,
                            cost_hint=1000 + (i * 7919) % 49000)
                  for i in range(300)]:
            f.result()
        fit = calibrate(cp.events, name="fitted-aws")
    fit_ok = (abs(fit.cold_start_s - true.cold_start_s)
              <= 0.25 * true.cold_start_s
              and abs(fit.warm_overhead_s - true.warm_overhead_s)
              <= 0.25 * true.warm_overhead_s
              and abs(fit.scaling_ramp_per_min
                      - true.scaling_ramp_per_min)
              <= 0.30 * true.scaling_ramp_per_min)

    assert events_total >= 100_000, events_total
    assert resident <= 4096, resident
    assert r_same.tasks == rec.tasks
    emit("trace_replay", rec.makespan_s * 1e6,
         nodes=rec.output, tasks=rec.tasks,
         events_total=events_total, resident_events=resident,
         recorded_vt_s=round(rec.makespan_s, 3),
         recorded_usd=round(rec.cost.total, 6),
         recorded_cold_starts=rec.cold_starts,
         replay_same_vt_s=round(r_same.makespan_s, 3),
         replay_parity_pct=round(parity_pct, 3),
         replay_gcf_vt_s=round(r_gcf.makespan_s, 3),
         replay_gcf_usd=round(r_gcf.cost.total, 6),
         gcf_slowdown_pct=round(
             100 * (r_gcf.makespan_s / rec.makespan_s - 1), 1),
         replay_ewma_vt_s=round(r_ewma.makespan_s, 3),
         replay_ewma_usd=round(r_ewma.cost.total, 6),
         ewma_resizes=len(r_ewma.autoscale_decisions),
         fitted_cold_s=round(fit.cold_start_s, 4),
         fitted_warm_ms=round(fit.warm_overhead_s * 1e3, 3),
         fitted_ramp_per_min=round(fit.scaling_ramp_per_min, 1),
         fit_within_tolerance=fit_ok,
         figure_png=("png" in arts),
         bounded_memory=resident <= 4096 < events_total)


# -- PR6: open-loop serving knee + SLO autoscale (repro.traffic) -----------------

def serving_knee() -> None:
    """Open-loop serving on the virtual-time harness: sweep the offered
    arrival rate over a fixed two-tenant mix (poisson chat + MMPP
    bursts, heavy-tailed lengths) on a static pool and report the p99
    TTFT *knee* — the rate where queueing takes over.  Then, at a
    bursty operating point, hold a p99 TTFT SLO with
    ``SLOAutoscalePolicy`` and compare provisioned cost-per-token
    against a static pool sized at the SLO run's own peak (the
    size-for-peak strawman).  The SLO run records to a spill-backed
    ``TraceStore`` and is replayed (same capacity schedule is not
    needed — the *static* comparator replays at its fixed width) with
    arrivals honoured; makespan and cost must land within 1 %.
    Everything is seeded: the whole row is bit-deterministic."""
    from repro.traffic import (ArrivalModel, EngineModel, LengthModel,
                               ResidencyConfig, SLOAutoscalePolicy,
                               TenantSpec, generate_stream, scale_rate,
                               serve_open_loop)
    from repro.trace import TraceStore, extract_workload, replay

    base = [
        TenantSpec("chat",
                   ArrivalModel(kind="poisson", rate=2.0),
                   prompt_len=LengthModel(mean=100.0, sigma=0.9,
                                          lo=8, hi=1024),
                   decode_len=LengthModel(mean=48.0, sigma=0.7,
                                          lo=4, hi=512)),
        TenantSpec("burst",
                   ArrivalModel(kind="mmpp", rate=0.5, burst_rate=6.0,
                                calm_s=10.0, burst_s=3.0),
                   prompt_len=LengthModel(kind="pareto", mean=160.0,
                                          alpha=1.4, lo=8, hi=2048),
                   decode_len=LengthModel(mean=32.0, sigma=0.8,
                                          lo=4, hi=256)),
    ]
    engine = EngineModel(prefill_s_per_token=5e-4,
                         decode_s_per_token=5e-3)
    prov = ProviderModel.aws_lambda()
    # memory-bounded host: overload must show up as *loss*, not just
    # queueing (FaaS_Sim A1/A2 become observable past the knee)
    rescfg = ResidencyConfig(memory_capacity_mb=48 * prov.memory_mb,
                             max_per_tenant=32)
    horizon, seed, static_cap = 60.0, 19, 8

    def run(factor, **kw):
        stream = generate_stream(scale_rate(base, factor),
                                 horizon_s=horizon, seed=seed)
        return serve_open_loop(stream, engine=engine, provider=prov,
                               residency_cfg=rescfg, **kw)

    t0 = time.monotonic()
    factors = (1, 2, 4, 8, 16)
    sweep = {f: run(f, capacity=static_cap) for f in factors}
    derived = {}
    for f, r in sweep.items():
        derived[f"x{f}_p99_ms"] = round(r.ttft_p99_s * 1e3, 2)
        derived[f"x{f}_loss_pct"] = round(100 * r.loss_rate, 2)
    base_p99 = sweep[factors[0]].ttft_p99_s
    knee = next((f for f in factors
                 if sweep[f].ttft_p99_s > 2 * base_p99), factors[-1])
    knee_visible = sweep[factors[-1]].ttft_p99_s > 3 * base_p99

    # bit-determinism: the same seeded config, end to end, twice
    deterministic = (run(knee, capacity=static_cap).as_dict()
                     == sweep[knee].as_dict())

    # SLO autoscale vs size-for-peak static, at the knee operating
    # point.  The target must exceed the capacity-independent TTFT
    # floor — cold start + the pareto tail's full prefill (~1.3 s
    # here) + the burst-onset queueing no reactive policy can preempt:
    # no autoscaler serves a 2048-token prompt's first token faster
    # than its prefill.  2.0 s is deliverable; the knee-rate static
    # pool violates it (the row asserts that), the SLO policy holds it.
    target = 2.0
    slo_trace = TraceStore(ring_size=4096)
    slo = run(knee, capacity=2, trace=slo_trace,
              autoscale=SLOAutoscalePolicy(
                  min_capacity=2, max_capacity=256,
                  target_p99_ttft_s=target, headroom=0.5,
                  grow_cooldown_s=0.25, shrink_cooldown_s=2.0))
    static_peak = run(knee, capacity=max(slo.peak_capacity, 3))
    slo_holds = slo.ttft_p99_s <= target
    slo_cheaper = (slo.provisioned_usd < static_peak.provisioned_usd
                   and slo.cost_per_token_usd
                   < static_peak.cost_per_token_usd)

    # record -> replay: the static knee run reproduces open-loop
    rep_trace = TraceStore(ring_size=4096)
    recorded = run(knee, capacity=static_cap, trace=rep_trace)
    wl = extract_workload(rep_trace)
    assert wl.open_loop, "serving trace must carry arrival offsets"
    replayed = replay(wl, max_concurrency=static_cap,
                      invoke_overhead=0.0)
    parity_pct = 100 * abs(replayed.makespan_s - recorded.makespan_s) \
        / recorded.makespan_s
    cost_parity_pct = 100 * abs(replayed.cost.total
                                - recorded.serverless_usd) \
        / max(recorded.serverless_usd, 1e-12)
    slo_trace.close()
    rep_trace.close()
    wall = time.monotonic() - t0

    emit("serving_knee", wall * 1e6,
         **derived,
         knee_factor=knee,
         knee_rate_rps=round(2.5 * knee, 2),
         knee_p50_ms=round(sweep[knee].ttft_p50_s * 1e3, 2),
         knee_p99_ms=round(sweep[knee].ttft_p99_s * 1e3, 2),
         knee_loss_pct=round(100 * sweep[knee].loss_rate, 2),
         knee_cost_per_mtok_usd=round(
             sweep[knee].cost_per_token_usd * 1e6, 4),
         slo_target_ms=round(target * 1e3, 1),
         slo_p99_ms=round(slo.ttft_p99_s * 1e3, 2),
         static_peak_p99_ms=round(static_peak.ttft_p99_s * 1e3, 2),
         slo_peak_capacity=slo.peak_capacity,
         slo_resizes=slo.resizes,
         slo_provisioned_usd=round(slo.provisioned_usd, 6),
         static_provisioned_usd=round(static_peak.provisioned_usd, 6),
         slo_cost_per_mtok_usd=round(slo.cost_per_token_usd * 1e6, 4),
         static_cost_per_mtok_usd=round(
             static_peak.cost_per_token_usd * 1e6, 4),
         slo_savings_pct=round(
             100 * (1 - slo.provisioned_usd
                    / max(static_peak.provisioned_usd, 1e-12)), 1),
         replay_parity_pct=round(parity_pct, 3),
         cost_parity_pct=round(cost_parity_pct, 3),
         knee_visible=knee_visible,
         deterministic=deterministic,
         static_knee_violates_target=sweep[knee].ttft_p99_s > target,
         slo_holds_target=slo_holds,
         slo_cheaper_than_static=slo_cheaper,
         replay_parity_ok=parity_pct <= 1.0 and cost_parity_pct <= 1.0)


# -- PR7: sharded master throughput ----------------------------------------------

def master_throughput() -> None:
    """Tasks/s *settled by the master* on a ~10^6-task sim frontier at
    ``shards`` ∈ {1, 4, 8}.

    The workload is a deterministic synthetic tree (hash-driven fanout,
    ~1.4M tasks) whose bodies are free — virtual time, echo execute —
    so the only cost is the master loop itself: future construction,
    trace emission, completion delivery, reduction.  ``shards=1`` is
    the legacy per-task loop (one SimFuture + one completion record +
    one trace event triple per task); ``shards=K`` runs the sharded
    driver with fused gather carriers and batched ``drain()`` delivery.
    The row asserts the PR's two gates: ≥4× settled throughput at
    ``shards=8`` and bit-identical outputs for shards=1 vs shards=8 on
    the real specs (UTS / Mariani-Silver / BC)."""
    from repro.trace import ShardedTraceStore, TraceStore

    ROOTS, DEPTH, MOD = 64, 13, 5

    def split(result, shape):
        nid, d = result
        if d >= DEPTH:
            return []
        base = nid * MOD
        return [((base + k) & 0x7FFFFFFFFFFFFFFF, d + 1)
                for k in range((nid * 2654435761 + d * 40503) % MOD)]

    from repro.core import WorkSpec
    spec = WorkSpec(
        name="synthetic-tree",
        seed=lambda shape=None: [(r, 0) for r in range(ROOTS)],
        execute=lambda item, shape: item,
        execute_batch=lambda items, shape: list(items),
        split=split,
        reduce=lambda total, r: total + 1,
        init=lambda: 0,
        finalize=lambda t: t,
        merge=lambda a, b: a + b,
    )

    def drive(shards):
        trace = (TraceStore(ring_size=4096) if shards == 1
                 else ShardedTraceStore(shards, ring_size=4096))
        with make_pool("sim", max_concurrency=1024, trace=trace) as pool:
            t0 = time.monotonic()
            r = run_irregular(pool, spec, batching=True,
                              shards=None if shards == 1 else shards)
            wall = time.monotonic() - t0
        trace.close()
        return r, wall

    outs, rates, derived = {}, {}, {}
    for k in (1, 4, 8):
        r, wall = drive(k)
        outs[k] = r.output
        rates[k] = r.tasks / wall
        derived[f"tasks_per_s_{k}"] = round(rates[k], 0)
        derived[f"wall_{k}_s"] = round(wall, 2)
    assert outs[1] == outs[4] == outs[8]

    # bit-identity on the real specs (small scale; BC per-task — fused
    # BC partials legitimately depend on chunk grouping)
    ident = {}
    for name, s, batching in (
            ("uts", uts_spec(UTSParams(seed=19, b0=4.0, max_depth=7,
                                       chunk=64)), True),
            ("ms", ms_spec(MSParams(width=128, height=128, max_dwell=64,
                                    initial_subdivision=4, max_depth=3)),
             True),
            ("bc", bc_spec(RMATParams(scale=6, edge_factor=4, seed=7),
                           n_tasks=16, regenerate_graph=True), False)):
        res = {}
        for k in (1, 8):
            with make_pool("sim", max_concurrency=64) as pool:
                res[k] = run_irregular(pool, s, batching=batching,
                                       shards=None if k == 1 else k
                                       ).output
        if name == "ms":
            ident[name] = bool(np.array_equal(res[1]["image"],
                                              res[8]["image"]))
        elif name == "bc":
            ident[name] = bool(np.array_equal(res[1], res[8]))
        else:
            ident[name] = res[1] == res[8]

    speedup_8 = rates[8] / rates[1]
    emit("master_throughput", 1e6 / rates[8],
         tasks_total=outs[1],
         tasks_per_s_settled=round(rates[8], 0),
         **derived,
         speedup_4x=round(rates[4] / rates[1], 2),
         speedup_8x=round(speedup_8, 2),
         master_scaling_ok=speedup_8 >= 4.0,
         identical_uts=ident["uts"], identical_ms=ident["ms"],
         identical_bc=ident["bc"],
         identical_outputs=all(ident.values()))


# -- Batch fusion: run_irregular with vs without execute_batch -------------------

def fig_batch_fusion() -> None:
    """Batched vs per-task execution on the sim pool (UTS + MS).

    Same WorkSpec, same virtual pool (few workers, FaaS-grade 13 ms
    invocation overhead); ``batching=True`` drains ready items through
    ``submit_batch`` into fused vectorized calls.  Outputs are asserted
    identical; the win is amortized per-invocation overhead (the
    application-level optimization lever of §5.2)."""
    cases = (
        ("uts", uts_spec(UTSParams(seed=19, b0=4.0, max_depth=8,
                                   chunk=2048)),
         dict(shape=TaskShape(16, 1000))),
        ("ms", ms_spec(MSParams(width=256, height=256, max_dwell=128,
                                initial_subdivision=4, max_depth=4)),
         dict()),
    )
    derived = {}
    us = 0.0  # headline: summed batched virtual time across the cases
    for name, spec, kw in cases:
        outs = {}
        for mode, batching in (("per_task", False), ("batched", True)):
            with make_pool("sim", max_concurrency=4,
                           invoke_overhead=13e-3) as pool:
                r = run_irregular(pool, spec, batching=batching, **kw)
                outs[mode] = (pool.virtual_time_s, r, pool.snapshot())
        vt_p, r_p, s_p = outs["per_task"]
        vt_b, r_b, s_b = outs["batched"]
        if name == "uts":
            assert r_p.output == r_b.output
        else:
            assert np.array_equal(r_p.output["image"],
                                  r_b.output["image"])
        us += vt_b * 1e6
        derived[f"{name}_per_task_vs"] = round(vt_p, 4)
        derived[f"{name}_batched_vs"] = round(vt_b, 4)
        derived[f"{name}_per_task_invocations"] = s_p["invocations"]
        derived[f"{name}_batched_invocations"] = s_b["invocations"]
        derived[f"{name}_speedup"] = round(vt_p / max(vt_b, 1e-12), 2)
    emit("fig_batch_fusion", us, **derived)


# -- Chaos: mortality tax, crash recovery, routing policies ----------------------

def chaos_mortality() -> None:
    """repro.chaos row (sim pool): the three fault-tolerance claims.

    1. **Mortality invariant** — 10% / 30% container mortality on a
       seeded ``FaultPlan`` leaves UTS / MS / BC outputs bit-identical
       (``chaos_identical_outputs``); what mortality buys is a makespan
       and cost *tax*, reported at 30%.
    2. **Crash recovery** — the master is killed mid-run at a seeded
       frontier depth (``kill_master_after``), the WAL journal is
       recovered, and ``resume_from=`` completes the run bit-identically
       (``resume_identical_outputs``) — including ``shards=3`` and
       ``batching=True``.  ``recovery_overhead_pct`` is the re-executed
       work: total tasks across killed + resumed runs over the
       uninterrupted run's.
    3. **Routing** — the deadline-aware ``CostPerDeadlinePolicy``
       against the legacy static cost_hint ``ThresholdPolicy`` on a
       bursty mixed-size stream (deterministic queueing model over the
       provider's cold/warm expectations).  Metric: billed elastic
       seconds per unit deadline-hit fraction — lower is better;
       ``routing_beats_threshold`` gates that the policy object earns
       its place.
    """
    from repro.chaos import (CostPerDeadlinePolicy, FaultPlan,
                             LocalFirstPolicy, MasterKilledError,
                             ThresholdPolicy, kill_master_after)

    t0 = time.monotonic()
    uts_p = UTSParams(seed=2, b0=3.0, max_depth=6)
    uts_kw = dict(shape=TaskShape(split_factor=4, iters=50))
    ms_p = MSParams(width=128, height=128, max_dwell=64, max_depth=4,
                    initial_subdivision=4)
    bc_p = RMATParams(scale=7, edge_factor=8, seed=2)

    def run(spec, faults=None, **kw):
        with make_pool("sim", max_concurrency=16, faults=faults) as pool:
            return run_irregular(pool, spec, **kw)

    cases = (
        ("uts", lambda: uts_spec(uts_p), uts_kw,
         lambda a, b: a == b),
        ("ms", lambda: ms_spec(ms_p), {},
         lambda a, b: bool(np.array_equal(a["image"], b["image"]))),
        ("bc", lambda: bc_spec(bc_p, n_tasks=24), {},
         lambda a, b: bool(np.array_equal(a, b))),
    )
    derived = {}
    identical = True
    makespan_tax = cost_tax = 0.0
    bases = {}
    for name, mk, kw, eq in cases:
        base = run(mk(), **kw)
        bases[name] = base
        for pct in (10, 30):
            plan = FaultPlan(seed=7, container_mortality=pct / 100)
            r = run(mk(), faults=plan, **kw)
            same = eq(r.output, base.output)
            identical = identical and same
            derived[f"{name}_identical_{pct}"] = bool(same)
            if pct == 30:
                derived[f"{name}_deaths_30"] = r.worker_deaths
                if name == "uts":
                    makespan_tax = (r.makespan_s / base.makespan_s
                                    - 1.0) * 100
                    cost_tax = (r.cost.total / base.cost.total
                                - 1.0) * 100
    derived["chaos_identical_outputs"] = bool(identical)
    derived["makespan_tax_30_pct"] = round(makespan_tax, 1)
    derived["cost_tax_30_pct"] = round(cost_tax, 1)

    # -- master kill + WAL resume ------------------------------------
    def kill_resume(mk, n_folds, eq, base, **kw):
        pool = make_pool("sim", max_concurrency=16)
        try:
            run_irregular(pool, kill_master_after(mk(), n_folds),
                          wal=True, **kw)
            raise RuntimeError("injected master kill never fired")
        except MasterKilledError:
            pass
        killed_tasks = pool.snapshot()["submitted"]
        trace = pool.events
        with make_pool("sim", max_concurrency=16) as pool2:
            r = run_irregular(pool2, mk(), resume_from=trace, **kw)
        pool.shutdown()
        return bool(eq(r.output, base.output)), killed_tasks, r

    resume_ok = True
    for label, mk, kw, eq, base in (
            ("uts", lambda: uts_spec(uts_p), uts_kw,
             cases[0][3], bases["uts"]),
            ("uts_shards", lambda: uts_spec(uts_p),
             dict(uts_kw, shards=3), cases[0][3], bases["uts"]),
            ("uts_batched", lambda: uts_spec(uts_p),
             dict(uts_kw, batching=True), cases[0][3], bases["uts"]),
            ("ms", lambda: ms_spec(ms_p), {}, cases[1][3], bases["ms"]),
            ("bc", lambda: bc_spec(bc_p, n_tasks=24), {}, cases[2][3],
             bases["bc"])):
        same, killed_tasks, r = kill_resume(mk, 5, eq, base, **kw)
        resume_ok = resume_ok and same
        derived[f"resume_identical_{label}"] = same
        if label == "uts":
            overhead = ((killed_tasks + r.tasks)
                        / max(1, bases["uts"].tasks) - 1.0) * 100
            derived["recovery_overhead_pct"] = round(overhead, 1)
            derived["recovered_tasks"] = r.recovered_tasks
    derived["resume_identical_outputs"] = bool(resume_ok)

    # -- routing policies on a bursty mixed-size stream --------------
    provider = ProviderModel.aws_lambda()
    deadline_s = 0.6
    tasks = [(burst * 1.0, 0.4 if i % 2 else 0.05)
             for burst in range(6) for i in range(8)]

    def route_sim(policy):
        class _Clk:
            t = 0.0

            def now(self):
                return self.t

        clk = _Clk()

        class _Local:
            max_concurrency = 4

            def __init__(self):
                self.ends = [0.0] * self.max_concurrency

            def idle_capacity(self):
                return sum(1 for e in self.ends if e <= clk.t)

            def pending(self):
                return 0

        class _Fleet:
            def __init__(self):
                self.ends = []

            def warm_count(self, now):
                return sum(1 for e in self.ends
                           if e <= now <= e + provider.keep_alive_s)

        class _Elastic:
            max_concurrency = 10_000

            def __init__(self):
                self.provider = provider
                self._fleet = _Fleet()
                self.clock = clk
                self.invoke_overhead = provider.warm_overhead_s

            def idle_capacity(self):
                return self.max_concurrency

            def pending(self):
                return 0

        class _SimHybrid:
            """Duck-typed ``.local``/``.elastic`` surface — routing
            policies read only the public pool attributes."""

            def __init__(self):
                self.local = _Local()
                self.elastic = _Elastic()

        h = _SimHybrid()
        billed = hits = 0.0
        for t_arr, hint in tasks:
            clk.t = t_arr
            body = hint  # alpha_s_per_cost = 1
            route = getattr(policy, "route", None)
            run_local = (route(h, cost_hint=hint) if route is not None
                         else policy(h))
            if run_local:
                i = min(range(len(h.local.ends)),
                        key=lambda j: h.local.ends[j])
                end = max(t_arr, h.local.ends[i]) + body
                h.local.ends[i] = end
            else:
                warm = h.elastic._fleet.warm_count(t_arr) > 0
                oh = provider.overhead_s(cold=not warm)
                end = t_arr + oh + body
                h.elastic._fleet.ends.append(end)
                billed += oh + body
            hits += 1.0 if end - t_arr <= deadline_s else 0.0
        hit_frac = hits / len(tasks)
        return billed, hit_frac, billed / max(hit_frac, 1e-9)

    policies = {
        "threshold": ThresholdPolicy(cost_threshold=0.2),
        "local_first": LocalFirstPolicy(),
        "cost_per_deadline": CostPerDeadlinePolicy(
            deadline_s=deadline_s, alpha_s_per_cost=1.0),
    }
    metrics = {}
    for name, pol in policies.items():
        billed, hit_frac, metric = route_sim(pol)
        metrics[name] = metric
        derived[f"route_{name}_billed_s"] = round(billed, 3)
        derived[f"route_{name}_hit_frac"] = round(hit_frac, 3)
        derived[f"route_{name}_metric"] = round(metric, 3)
    derived["routing_beats_threshold"] = bool(
        min(m for n, m in metrics.items() if n != "threshold")
        < metrics["threshold"])

    emit("chaos_mortality", (time.monotonic() - t0) * 1e6, **derived)


# -- Roofline table (from the dry-run artifacts) ----------------------------------

def roofline_from_dryrun() -> None:
    root = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun")
    if not os.path.isdir(root):
        emit("roofline_from_dryrun", 0.0, status="no dryrun artifacts")
        return
    n = 0
    for arch in sorted(os.listdir(root)):
        for shape in sorted(os.listdir(os.path.join(root, arch))):
            f = os.path.join(root, arch, shape, "pod256.json")
            if not os.path.exists(f):
                continue
            rec = json.load(open(f))
            if rec.get("status") != "ok":
                continue
            a = rec.get("analysis", {})
            if "compute_s" not in a:
                continue
            n += 1
            emit(f"roofline[{arch}/{shape}]",
                 a["compute_s"] * 1e6,
                 compute_s=round(a["compute_s"], 4),
                 memory_s=round(a["memory_s"], 4),
                 collective_s=round(a["collective_s"], 4),
                 dominant=a["dominant"])
    emit("roofline_from_dryrun", 0.0, cells=n)


# -- DAG workloads: dependency-structured pipelines (repro.dag) -------------------

def dag_pipeline() -> None:
    """The three shipped DAG families on the sim pool: plain vs fused
    dispatch (and a wall-clock thread pool) must fold to identical sink
    values; reports the graph-shape metrics the DAG driver surfaces."""
    from repro.dag import (hyperparam_sweep_dag, iterative_mapreduce_dag,
                           montage_dag)
    t0 = time.monotonic()
    derived = {}
    identical = True
    families = (
        ("montage", montage_dag, {"tiles": 32}),
        ("sweep", hyperparam_sweep_dag, {"configs": 16, "stages": 4}),
        ("iter_mr", iterative_mapreduce_dag,
         {"rounds": 5, "initial_width": 12}),
    )
    for key, mk, kw in families:
        plain = run_irregular(make_pool("sim", max_concurrency=32),
                              mk(**kw))
        fused = run_irregular(make_pool("sim", max_concurrency=32),
                              mk(**kw), batching=True)
        lpool = make_pool("local", max_concurrency=4)
        try:
            wall = run_irregular(lpool, mk(**kw))
        finally:
            lpool.shutdown()
        identical = identical and (
            plain.output == fused.output == wall.output)
        derived[f"{key}_nodes"] = plain.dag_nodes
        derived[f"{key}_critical_path"] = plain.critical_path_len
        derived[f"{key}_max_stage_width"] = max(plain.stage_widths)
        derived[f"{key}_vt_s"] = round(plain.makespan_s, 4)
        derived[f"{key}_vt_fused_s"] = round(fused.makespan_s, 4)
    derived["dag_identical_outputs"] = bool(identical)
    emit("dag_pipeline", (time.monotonic() - t0) * 1e6, **derived)


# -- Barcelona-Pons parallelism probe (repro.dag.probe) ---------------------------

def faas_parallelism() -> None:
    """Simultaneous-invocation bursts at geometric widths against the
    provider presets (achieved-vs-requested concurrency, ramp latency,
    cold share), plus the gated fit-recovery check: a constant-width
    probe of a known preset must let ``fit_provider`` recover its
    burst/ramp/cold-start within tolerance."""
    import dataclasses as _dc
    from repro.dag import run_parallelism_probe
    t0 = time.monotonic()
    derived = {}
    monotone = True
    for preset in ("aws_lambda", "gcf", "azure_functions", "prewarmed"):
        provider = getattr(ProviderModel, preset)()
        pool = make_pool("sim", max_concurrency=2048, provider=provider)
        prof = run_parallelism_probe(pool, max_width=512)
        monotone = monotone and prof.envelope_monotone()
        last = prof.bursts[-1]
        derived[f"{preset}_achieved_at_512"] = last.achieved
        derived[f"{preset}_ramp_latency_s"] = round(last.ramp_latency_s, 3)
        derived[f"{preset}_cold_share"] = round(last.cold_start_share, 3)
    derived["probe_envelope_monotone"] = bool(monotone)
    known = _dc.replace(ProviderModel.gcf(), name="probe-target",
                        burst_concurrency=8, scaling_ramp_per_min=240.0,
                        cold_start_s=0.3)
    pool = make_pool("sim", max_concurrency=1024, provider=known)
    prof = run_parallelism_probe(pool, max_width=256, start=256,
                                 repeats_at_max=10)
    fitted = prof.fit(base=known)
    derived["fit_burst"] = fitted.burst_concurrency
    derived["fit_ramp_per_min"] = round(fitted.scaling_ramp_per_min, 1)
    derived["fit_cold_s"] = round(fitted.cold_start_s, 4)
    derived["probe_fit_recovers"] = bool(
        abs(fitted.burst_concurrency - 8) <= 2
        and abs(fitted.scaling_ramp_per_min - 240.0) / 240.0 < 0.25
        and abs(fitted.cold_start_s - 0.3) / 0.3 < 0.25)
    emit("faas_parallelism", (time.monotonic() - t0) * 1e6, **derived)


BENCHES = {
    "table1": table1_uts_tree_sizes,
    "table2": table2_characterization,
    "table4": table4_invocation_overheads,
    "table5": table5_uts_performance,
    "fig4": fig4_dynamic_optimization,
    "fig4_sim": fig4_dynamic_optimization_sim,
    "fig5_table6": fig5_table6_mariani_silver,
    "fig6": fig6_bc_scaling,
    "fig7_9": fig7_9_cost_performance,
    "cost_perf_sim": cost_performance_sim,
    "cold_warm": cold_warm_ablation,
    "fig_batch_fusion": fig_batch_fusion,
    "master_throughput": master_throughput,
    "trace_replay": trace_record_replay,
    "serving_knee": serving_knee,
    "chaos_mortality": chaos_mortality,
    "dag_pipeline": dag_pipeline,
    "faas_parallelism": faas_parallelism,
    "roofline": roofline_from_dryrun,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", choices=list(BENCHES))
    ap.add_argument("--json", metavar="PATH",
                    help="also write rows as structured JSON "
                         "(name, us_per_call, derived kv) for "
                         "cross-PR perf tracking")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        try:
            BENCHES[name]()
        except Exception as e:  # noqa: BLE001 — keep the harness going
            emit(name, 0.0, status=f"ERROR {type(e).__name__}: {e}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(JSON_ROWS, f, indent=2, sort_keys=True)
            f.write("\n")
    fails = [r for r in ROWS if "ERROR" in r]
    if fails:
        sys.exit(1)


if __name__ == "__main__":
    main()
