"""Smoke run of the system's main path on one TPU chip.

The main path is an autoscaling episode: ``AutoScaler.run`` over a
``StreamEngine`` whose stateful operators keep their state in ``LSMStore``.
Here the store's probes and consolidation weight sums run as compiled
Pallas kernels (``kernel_impl="pallas"``).  Phases:

  (a) device    require a TPU; place the persistent compile cache;
  (b) store     two stores, numpy and pallas, bulk-loaded with NEXmark
                q8's warm-up state (2.4M join entries), answer the same
                reads and writes; values, found masks, metrics and the
                final weighted contents must be exactly equal;
  (c) episode   the q8-justin golden episode on the pallas store must match
                ``tests/data/golden_autoscale.json`` field by field;
  (d) report    episode seconds, device calls and compiled programs per
                kernel, and compile seconds.

Any failure raises.  The last line of standard output is one JSON object
naming the device, printed only when every phase passed.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

GOLDEN = json.loads((ROOT / "tests" / "data" / "golden_autoscale.json")
                    .read_text())
SEED = 3
INT64 = np.iinfo(np.int64)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


def device() -> dict:
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX runs on {d.platform!r})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileClock:
    """Sums the backend compile seconds JAX reports (persistent-cache
    retrievals included)."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def assert_same_read(a, b, keys: np.ndarray, what: str) -> None:
    va, fa = a.get_batch(keys)
    vb, fb = b.get_batch(keys)
    np.testing.assert_array_equal(fa, fb, err_msg=f"found mask: {what}")
    np.testing.assert_array_equal(va, vb, err_msg=f"values: {what}")
    assert a.metrics.snapshot() == b.metrics.snapshot(), f"metrics: {what}"
    log(f"  store read {what}: {len(keys)} keys, {int(fa.sum())} found, "
        f"equal")


def store_parity() -> None:
    """Phase (b): q8's warm-up state through the q8 join's own
    ``warm_state`` (``bulk_load``), then reads and writes on both
    backends."""
    from repro.data.nexmark import QUERIES
    from repro.state.lsm import LSMStore
    from repro.streaming.engine import BASE_MEM_MB
    from repro.streaming.events import PAYLOAD_WORDS
    join = QUERIES["q8"]().nodes["window_join"].op
    stores = []
    for impl in ("numpy", "pallas"):
        st = LSMStore(BASE_MEM_MB, value_words=PAYLOAD_WORDS,
                      entry_bytes=join.entry_bytes, kernel_impl=impl)
        join.warm_state(st, np.random.default_rng(SEED))
        stores.append(st)
    a, b = stores
    keys = a.levels[0][0]
    log(f"  store loaded: {len(keys)} entries, keys in "
        f"[{int(keys[0])}, {int(keys[-1])}]")
    rng = np.random.default_rng(SEED)
    present = rng.choice(keys, 4096)
    assert_same_read(a, b, present, "present keys")
    assert_same_read(a, b, present + 2, "absent keys")   # window id 2: absent
    top = keys[-4096:]
    assert int(top[0]) > 2**31
    assert_same_read(a, b, np.concatenate([top, top + 3]),
                     "keys above 2^31")
    assert_same_read(a, b, np.array([-5, -(1 << 40), INT64.min, -int(top[0])],
                                    np.int64), "negative keys")
    assert_same_read(a, b, np.array([INT64.max, INT64.max - 1, 0], np.int64),
                     "int64 extremes")
    # writes: duplicate-heavy batches force consolidations (device weight
    # sums, some weights past bfloat16's 256) and a memtable flush
    for i in range(20):
        w = np.concatenate([rng.choice(keys, 9_000), np.full(1_000, keys[i])])
        vals = rng.integers(0, 2**31 - 1, (len(w), PAYLOAD_WORDS),
                            dtype=np.int64).astype(np.int32)
        a.put_batch(w, vals)
        b.put_batch(w, vals)
        if i % 5 == 4:
            assert_same_read(a, b, rng.choice(w, 2048), f"after write {i}")
    assert a.metrics.flushes >= 1, a.metrics.snapshot()
    sa, sb = a.snapshot(), b.snapshot()
    for f in ("keys", "weights", "vals"):
        np.testing.assert_array_equal(sa[f], sb[f], err_msg=f"snapshot {f}")
    log(f"  store contents equal: {len(sa['keys'])} keys, max weight "
        f"{int(sa['weights'].max())}, metrics {a.metrics.snapshot()}")


def episode() -> tuple[dict, float]:
    """Phase (c): the q8-justin golden recipe (seed and ``max_level`` from
    the golden file, the policy built through the registry)."""
    from repro.core.controller import AutoScaler, ControllerConfig
    from repro.core.justin import JustinParams
    from repro.core.policy import make_policy
    from repro.data.nexmark import QUERIES, TARGET_RATES
    from repro.state import lsm
    from repro.streaming.engine import StreamEngine
    meta = GOLDEN["_meta"]
    lsm.set_kernel_impl("pallas")
    eng = StreamEngine(QUERIES["q8"](), seed=meta["seed"])
    cfg = ControllerConfig(policy="justin",
                           justin=JustinParams(max_level=meta["max_level"]))
    ctl = AutoScaler(eng, TARGET_RATES["q8"], cfg,
                     policy=make_policy("justin", cfg))
    t0 = time.perf_counter()
    hist = ctl.run()
    seconds = time.perf_counter() - t0
    return {
        "steps": ctl.steps,
        "windows": len(hist),
        "configs": [sorted([op, list(pc)] for op, pc in h.config.items())
                    for h in hist],
        "triggered": [h.triggered for h in hist],
        "cpu_cores": hist[-1].cpu_cores,
        "memory_mb": hist[-1].memory_mb,
        "final_rate_ok": bool(hist[-1].achieved_rate
                              >= 0.97 * TARGET_RATES["q8"]),
    }, seconds


def main() -> None:
    dev = device()
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")
    from repro.kernels import device as kdev
    from repro.obs import counts
    log(f"compile cache: {kdev.use_compile_cache()}")
    clock = CompileClock()
    from repro.kernels.sorted_probe.kernel import sorted_probe
    from repro.kernels.window_agg.kernel import window_agg

    log("phase b: store parity at q8 warm-up size")
    t0 = time.perf_counter()
    store_parity()
    log(f"  store phase seconds: {time.perf_counter() - t0:.3f}")

    log("phase c: q8-justin episode on the pallas store")
    calls0, comp0 = dict(counts), (clock.seconds, clock.count)
    got, seconds = episode()
    want = GOLDEN["q8_justin"]
    for field in ("steps", "windows", "configs", "triggered", "cpu_cores",
                  "memory_mb", "final_rate_ok"):
        want_v = want[field] if field != "windows" else len(want["configs"])
        assert got[field] == want_v, (field, got[field], want_v)
        log(f"  {field}: {got[field]} == golden")

    log("phase d: report")
    log(f"  episode wall seconds: {seconds:.3f}")
    for k in ("sorted_probe.calls", "window_agg.calls"):
        n = counts[k]
        log(f"  device calls {k}: episode {n - calls0.get(k, 0)}, "
            f"whole run {n}")
    programs = {"sorted_probe": sorted_probe._cache_size(),
                "window_agg": window_agg._cache_size()}
    log(f"  compiled programs per kernel (whole run): {programs}, "
        f"total {sum(programs.values())}")
    log(f"  backend compiles: episode {clock.count - comp0[1]} in "
        f"{clock.seconds - comp0[0]:.3f} s; whole run {clock.count} in "
        f"{clock.seconds:.3f} s")
    assert sum(programs.values()) < 100, programs
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
