"""Quickstart: the three layers of the framework in one script.

1. train a reduced assigned-architecture for a few steps (CPU),
2. run the Justin autoscaler on a Nexmark query vs the DS2 baseline,
3. validate one Pallas kernel against its oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

print("=== 1. train a reduced arch (llama3.2-3b family) ===")
from repro.launch.train import train                      # noqa: E402

result = train("llama3.2-3b", steps=10, verbose=False)
print(f"10 steps: loss {result['first_loss']:.3f} -> "
      f"{result['final_loss']:.3f} ({result['wall_s']}s)\n")

print("=== 2. Justin vs DS2 on Nexmark q11 ===")
from repro.core.controller import AutoScaler, ControllerConfig  # noqa: E402
from repro.core.justin import JustinParams                # noqa: E402
from repro.core.policy import available_policies          # noqa: E402
from repro.data.nexmark import QUERIES, TARGET_RATES      # noqa: E402
from repro.streaming.engine import StreamEngine           # noqa: E402

print(f"(registered scaling policies: {', '.join(available_policies())})")
for policy in ("ds2", "justin"):
    eng = StreamEngine(QUERIES["q11"](), seed=3)
    ctl = AutoScaler(eng, TARGET_RATES["q11"], ControllerConfig(
        policy=policy, justin=JustinParams(max_level=2)))
    ctl.run()
    s = ctl.summary()
    print(f"{policy:6s}: steps={s['steps']} "
          f"rate={s['achieved_rate']:,.0f}/{s['target']:,} "
          f"cpu={s['cpu_cores']} cores mem={s['memory_mb']:,.0f} MB "
          f"config={ {k: v for k, v in s['config'].items() if k != 'source'} }")
print()

print("=== 3. Pallas kernel vs oracle (sorted-run probe) ===")
from repro.kernels.sorted_probe.ops import probe          # noqa: E402

rng = np.random.default_rng(0)
table = np.unique(rng.integers(0, 1 << 20, 4096))
queries = rng.integers(0, 1 << 20, 512)
p1, f1 = probe(table, queries, impl="interpret")  # Pallas interpreter (CPU)
p2, f2 = probe(table, queries, impl="ref")        # numpy oracle
print(f"positions match: {bool((p1 == p2).all())}, "
      f"found match: {bool((f1 == f2).all())}")
