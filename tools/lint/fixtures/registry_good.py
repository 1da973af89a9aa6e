# as: src/repro/serve/registry_good.py
"""Known-good registry-discipline fixture: registry construction paths
and read-only history access."""
from repro.core.policy import ScalingPolicy, make_policy, register_policy


def build(cfg):
    policy = make_policy(cfg.policy)
    return policy


@register_policy("shadow")
class ShadowPolicy(ScalingPolicy):
    def decide(self, window):
        return None


def read_history(run):
    latest = run.history[-1]
    return latest.admitted
