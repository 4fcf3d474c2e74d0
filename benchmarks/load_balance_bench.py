"""§3.4 benchmark: owner-assignment quality per strategy on every assigned
architecture's real shape census (analytic TPU cost model), plus MILP vs
greedy solve time."""

from __future__ import annotations

import time

import jax

from benchmarks.common import csv_row
from repro import configs
from repro.core import api, load_balance
from repro.models import model_fns

RANKS = 64


def census_for(arch_id: str):
    cfg = configs.get(arch_id)
    shapes = jax.eval_shape(lambda k: model_fns(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    plan = api.dedicate_params(shapes, num_owners=1, strategy="round_robin")
    census = {}
    for g in plan.groups.values():          # aggregate per-leaf groups by shape
        census[g.key] = census.get(g.key, 0) + g.count
    return census


def run() -> list[str]:
    rows = []
    for arch in ("qwen2.5-14b", "kimi-k2-1t-a32b", "hymba-1.5b"):
        census = census_for(arch)
        cm = load_balance.analytic_cost_model(census)
        lower = sum(cm.per_matrix(s) * n for s, n in census.items()) / RANKS
        for strat in ("load_balance", "greedy", "lpt", "round_robin",
                      "rank0"):
            t0 = time.perf_counter()
            asn = load_balance.assign(census, RANKS, strategy=strat,
                                      cost_model=cm, s_thr=2000)
            dt = time.perf_counter() - t0
            mk = asn.makespan(cm)
            rows.append(csv_row(
                f"lb/{arch}/{strat}/makespan", mk * 1e6,
                derived=f"vs_lower_bound={mk/lower:.2f}x solve={dt:.3f}s"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(r)
