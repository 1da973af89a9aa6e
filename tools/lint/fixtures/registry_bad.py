# as: src/repro/serve/registry_bad.py
"""Known-bad registry-discipline fixture: string dispatch on .policy,
an unregistered policy, history patching."""
from repro.core.policy import ScalingPolicy


def build(cfg):
    if cfg.policy == "justin":                       # expect: R301
        mode = "hybrid"
    elif cfg.policy in ("ds2", "static"):            # expect: R301
        mode = "cpu-only"
    return mode


class ShadowPolicy(ScalingPolicy):                   # expect: R303
    def decide(self, window):
        return None


def patch_history(run):
    run.history[-1].admitted = True                  # expect: R304
    row = run.history[-1]
    row.downtime_s = 0.0                             # expect: R304
