"""Quickstart: plan a batching strategy and serve a small MoE with it.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import planner
from repro.core.dag_builder import Plan
from repro.core.engine import ModuleBatchingEngine
from repro.core.hardware import A5000_C2
from repro.models import model as M


def main() -> None:
    # 1. the paper's model (full config) + its planned strategy on C2
    cfg_full = get_config("mixtral-8x7b")
    res = planner.search_decode(cfg_full, A5000_C2, ctx=768)
    print("planned strategy for", cfg_full.name)
    print("   ", res.plan.describe())
    print(f"    predicted decode throughput: "
          f"{res.estimate.throughput:.0f} tokens/s "
          f"({res.evaluated} configs searched)")
    print("    critical path:", " -> ".join(res.estimate.critical[:5]))

    # 2. execute module-based batching for real on a smoke-scale variant
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S, DEC = 8, 32, 12
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size
    )
    plan = Plan(B=B, b_a=4, b_e=64, omega=0.0)
    engine = ModuleBatchingEngine(cfg, params, plan, max_seq=S + DEC)
    tokens = engine.generate(prompts, DEC)
    print("\nengine generated", tokens.shape, "tokens")
    print("   stats:", engine.stats)


if __name__ == "__main__":
    main()
