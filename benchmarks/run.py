"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Runs the QUICK variants so the
whole suite finishes in minutes; the full grids live in microbench_grid.py /
nexmark_eval.py / roofline.py (see EXPERIMENTS.md for full-run outputs).

All wall-clock measurement goes through ONE registry
(``repro.obs.MetricsRegistry.timer``) instead of ad-hoc ``time.time()``
pairs, so the CSV rows, the BENCH_*.json artifacts and any recorded
traces report from the same clock path; ``fleet`` additionally accepts
``--trace PATH`` to dump the fleet episode's span trace as JSONL.
"""
from __future__ import annotations

import sys

_REG = None


def _registry():
    """The suite-wide metrics registry (lazy: ``repro`` imports stay
    inside bench functions so ``py_compile`` needs no PYTHONPATH)."""
    global _REG
    if _REG is None:
        from repro.obs import MetricsRegistry
        _REG = MetricsRegistry()
    return _REG


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_fig4_microbench() -> None:
    """Paper Fig. 4: memory/parallelism grid (quick subset)."""
    from benchmarks.microbench_grid import run_point
    for mode, p, mem in [("read", 1, 128), ("read", 4, 1024),
                         ("read", 8, 512), ("write", 4, 512),
                         ("update", 8, 512)]:
        with _registry().timer(f"fig4_{mode}_p{p}_m{mem}") as tm:
            r = run_point(mode, p, mem, seconds=6)
        _row(f"fig4_{mode}_p{p}_m{mem}", tm.us,
             f"rate={r['rate']:.0f};sustained={r['sustained']};"
             f"theta={r['theta'] if r['theta'] is not None else ''}")


def bench_fig5_nexmark() -> None:
    """Paper Fig. 5 / §5.1: Justin vs DS2 (q11 + q1, quick)."""
    from benchmarks.nexmark_eval import evaluate
    with _registry().timer("fig5_nexmark") as tm:
        res = evaluate(["q1", "q11"], max_level=2, verbose=False)
    for q, row in res["queries"].items():
        _row(f"fig5_{q}", tm.us / len(res["queries"]),
             f"cpu_saving={row['cpu_saving']:.2f};"
             f"mem_saving={row['mem_saving']:.2f};"
             f"steps={row['steps_justin_vs_ds2']}")


def bench_episode_autoscale() -> None:
    """Single-episode autoscaling wall-clock — the engine fast-path
    headline number (one full AutoScaler episode per policy, q11)."""
    from repro.core.controller import AutoScaler, ControllerConfig
    from repro.core.justin import JustinParams
    from repro.data.nexmark import QUERIES, TARGET_RATES
    from repro.streaming.engine import StreamEngine
    for policy in ("ds2", "justin"):
        with _registry().timer(f"episode_q11_{policy}") as tm:
            flow = QUERIES["q11"]()
            eng = StreamEngine(flow, seed=3)
            ctl = AutoScaler(eng, TARGET_RATES["q11"], ControllerConfig(
                policy=policy, justin=JustinParams(max_level=2)))
            ctl.run()
            s = ctl.summary()
        _row(f"episode_q11_{policy}", tm.us,
             f"steps={s['steps']};rate={s['achieved_rate']:.0f};"
             f"cpu={s['cpu_cores']};mem={s['memory_mb']:.0f}")


def bench_scenarios() -> None:
    """Dynamic-workload scenarios through the controller — one episode per
    registered policy family (model-based justin, reactive threshold,
    fixed static) plus justin under a spike."""
    from repro.scenarios import run_scenario
    for policy, prof in (("justin", "ramp"), ("justin", "spike"),
                         ("threshold", "ramp"), ("static", "ramp")):
        with _registry().timer(f"scenario_q5_{prof}_{policy}") as tm:
            r = run_scenario(policy, "q5", prof, windows=6)
        _row(f"scenario_q5_{prof}_{policy}", tm.us,
             f"steps={r.steps};recovered={r.recovered()};"
             f"cpu={r.final.cpu_cores}")


def bench_colocation() -> None:
    """Shared-cluster co-location: the neighbor a ds2 tenant blocks is
    admitted when the tenant runs justin (see examples/colocation_demo.py)."""
    from repro.core.controller import ControllerConfig
    from repro.core.justin import JustinParams
    from repro.scenarios import Cluster, ColocatedSpec, run_colocated
    cfg = ControllerConfig(decision_window_s=60.0, stabilization_s=30.0,
                           justin=JustinParams(max_level=2))
    for a_policy in ("ds2", "justin"):
        with _registry().timer(f"colocate_A_{a_policy}") as tm:
            res = run_colocated(
                [ColocatedSpec(a_policy, "q1", name="A"),
                 ColocatedSpec("ds2", "q1", name="B")],
                Cluster(cpu_slots=16, memory_mb=7000.0), windows=5, cfg=cfg)
        b = res.tenant("B")
        _row(f"colocate_A_{a_policy}", tm.us,
             f"B_denied={len(b.denials)};B_recovered={b.slo().recovered};"
             f"peak_mem={max(m for _, m in res.usage):.0f}")
    # preemptive admission: a static tenant pinned at storage level 2
    # starves the high-priority DS2 tenant under priority; preemption
    # reclaims its levels and the request is admitted
    for adm in ("priority", "preemption"):
        with _registry().timer(f"colocate_preempt_{adm}") as tm:
            res = run_colocated(
                [ColocatedSpec("ds2", "q1", name="H"),
                 ColocatedSpec("static", "q11", name="V", target=5_000,
                               config={"user_sessions": (6, 2)})],
                Cluster(cpu_slots=16, memory_mb=8500.0), windows=5, cfg=cfg,
                admission=adm)
        h, v = res.tenant("H"), res.tenant("V")
        _row(f"colocate_preempt_{adm}", tm.us,
             f"H_denied={len(h.denials)};V_preempted={len(v.preemptions)};"
             f"H_recovered={h.slo().recovered}")
    # shared-TM packing: three small tenants on one slot-capped fleet pay
    # two TMs' base memory instead of three private fleets'
    from repro.core.placement import default_tm_spec
    with _registry().timer("colocate_shared_tm") as tm:
        cluster = Cluster(cpu_slots=6, memory_mb=20000.0,
                          tm_spec=default_tm_spec())
        res = run_colocated([("ds2", "q1")] * 3, cluster, windows=2, cfg=cfg)
    shared = cluster.placement().memory_mb
    private = sum(t.scaler.resources()[1] for t in res.tenants)
    _row("colocate_shared_tm", tm.us,
         f"shared_mb={shared:.0f};private_mb={private:.0f};"
         f"saving={1 - shared / private:.2f}")


def bench_fleet() -> None:
    """Thousand-tenant fleet driver: vectorized ``run_colocated`` over a
    sampled population, headline = simulated tenant-windows per second.
    Writes ``BENCH_cluster.json`` (schema checked by tools/check_bench.py)
    with the registry snapshot (timers + fleet audit totals) under
    ``obs``.

    Scale: ``run.py fleet [tenants windows] [--trace PATH]`` (default
    1000 x 100); ``--trace`` records the preemption episode's span trace
    as JSONL (schema checked by tools/check_trace.py).  When the whole
    suite runs (no selector) the quick 128 x 20 variant keeps the total
    under a minute."""
    import json
    import os

    from repro.scenarios import fleet_stats, run_fleet
    argv = sys.argv[1:]
    trace_path = None
    if "--trace" in argv:
        i = argv.index("--trace")
        trace_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if argv and argv[0] == "fleet":
        tenants = int(argv[1]) if len(argv) > 1 else 1000
        windows = int(argv[2]) if len(argv) > 2 else 100
    else:
        tenants, windows = 128, 20
    reg = _registry()
    tracer = None
    runs = []
    for admission in ("fair_share", "preemption"):
        if trace_path is not None and admission == "preemption":
            from repro.obs import Tracer
            tracer = Tracer(enabled=True)
        with reg.timer(f"fleet_{admission}_{tenants}x{windows}") as tm:
            res = run_fleet(tenants, windows, admission=admission, seed=0,
                            tracer=tracer if admission == "preemption"
                            else None)
        st = fleet_stats(res, tm.s)
        st["driver"] = "vectorized"
        st["seed"] = 0
        runs.append(st)
        reg.absorb_fleet(res, prefix=f"fleet.{admission}")
        _row(f"fleet_{admission}_{tenants}x{windows}",
             st["seconds"] * 1e6,
             f"tw_per_s={st['tenant_windows_per_s']:.0f};"
             f"denied={st['denied_tenant_windows']};"
             f"deferred={st['deferred_tenant_windows']};"
             f"preempted={st['preempted_tenant_windows']}")
    if tracer is not None:
        from repro.obs import write_jsonl
        write_jsonl(tracer.spans, trace_path,
                    meta={"bench": "cluster_fleet", "tenants": tenants,
                          "windows": windows, "admission": "preemption",
                          "seed": 0})
        print(f"wrote {trace_path} ({len(tracer.spans)} spans)", flush=True)
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_cluster.json")
    with open(path, "w") as f:
        json.dump({"bench": "cluster_fleet", "schema_version": 1,
                   "runs": runs, "obs": reg.snapshot()}, f, indent=2)
        f.write("\n")


def bench_justinserve() -> None:
    """Beyond-paper: hybrid LLM-serving elasticity."""
    from benchmarks.justinserve_bench import evaluate
    with _registry().timer("justinserve") as tm:
        res = evaluate(verbose=False)
    _row("justinserve", tm.us,
         f"replica_saving={res['replica_saving']:.2f};"
         f"justin_replicas={res['justin']['replicas']};"
         f"ds2_replicas={res['ds2']['replicas']}")


def bench_kernels() -> None:
    """Pallas kernels vs their oracles (interpret mode, correctness +
    per-call wall time on this CPU host)."""
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    reg = _registry()

    from repro.kernels.sorted_probe.ops import probe
    table = np.unique(rng.integers(0, 1 << 20, 4096))
    queries = rng.integers(0, 1 << 20, 1024)
    p1, f1 = probe(table, queries, impl="interpret")
    with reg.timer("kernel_sorted_probe") as tm:
        p1, f1 = probe(table, queries, impl="interpret")
    p2, f2 = probe(table, queries, impl="ref")
    _row("kernel_sorted_probe", tm.us,
         f"match={bool((p1 == p2).all() and (f1 == f2).all())}")

    from repro.kernels.window_agg.ops import aggregate
    seg = rng.integers(0, 512, 2048).astype(np.int32)
    vals = rng.normal(size=(2048, 4)).astype(np.float32)
    s1, c1 = aggregate(seg, vals, 512, impl="interpret")
    with reg.timer("kernel_window_agg") as tm:
        s1, c1 = aggregate(seg, vals, 512, impl="interpret")
    s2, c2 = aggregate(seg, vals, 512, impl="ref")
    _row("kernel_window_agg", tm.us,
         f"allclose={bool(np.allclose(s1, s2, atol=1e-3))}")

    from repro.kernels.flash_attn.ops import attention
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    o1 = attention(q, k, v)
    with reg.timer("kernel_flash_attn") as tm:
        o1 = attention(q, k, v)
    o2 = attention(q, k, v, impl="ref")
    _row("kernel_flash_attn", tm.us,
         f"maxerr={float(jnp.max(jnp.abs(o1 - o2))):.2e}")

    from repro.kernels.decode_attn.ops import decode
    qd = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(2, 2, 512, 64)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(2, 2, 512, 64)), jnp.float32)
    o1 = decode(qd, kc, vc, 512)
    with reg.timer("kernel_decode_attn") as tm:
        o1 = decode(qd, kc, vc, 512)
    o2 = decode(qd, kc, vc, 512, impl="ref")
    _row("kernel_decode_attn", tm.us,
         f"maxerr={float(jnp.max(jnp.abs(o1 - o2))):.2e}")


def bench_train_smoke() -> None:
    """End-to-end reduced training step timing per arch family."""
    from repro.launch.train import train
    for arch in ("llama3.2-3b", "mamba2-130m", "mixtral-8x7b"):
        with _registry().timer(f"train_{arch}") as tm:
            r = train(arch, steps=4, verbose=False)
        _row(f"train_{arch}", tm.us / 4,
             f"final_loss={r['final_loss']:.3f}")


def main() -> None:
    print("name,us_per_call,derived")
    only = sys.argv[1] if len(sys.argv) > 1 else None
    for name, fn in list(globals().items()):
        if name.startswith("bench_") and (only is None or only in name):
            fn()


if __name__ == "__main__":
    main()
