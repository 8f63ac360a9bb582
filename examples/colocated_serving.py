"""End-to-end driver (the paper's kind: SERVING): co-located LLM serving
through the event-driven day cycle.

A small cluster runs the paper's §1/§2.3 scenario on the co-location event
loop (`repro.core.colocation`): diurnal online traffic scales a chat
service up and down through `AutoscalePolicy` event sources, offline batch
jobs pad the valleys via chunked ``plan_batch`` admission, the morning ramp
preempts offline victims (which re-enter the pending queue and are
replanned when capacity reopens), and every committed decision streams
through the scheduler listeners into a per-hour `ColocationReport`.

On the committed 24-node benchmark day (``BENCH_colocation.json``) the
topology-aware engine beats the topology-unaware baseline by ~9% on the
whole-day scheduled-performance integral and by ~50% on the
preemption-scheduled slice — the same direction and order as the paper's
headline 55% claim.

The same day then runs through the two-level backfill ladder
(`repro.serving.elastic` + ``ColocationConfig(elastic=True)``): valley
ticks pack pending offline jobs into online replicas' spare
continuous-batching slots under the SLO-guarded admission controller
before spinning whole offline instances, and peak ramps reverse the
ladder — eject request-level grants, then demote whole offline instances
into request slots, and only preempt what neither step absorbs.  On the
committed day (``BENCH_elastic.json``) that strictly raises offline
goodput at equal online SLO attainment with strictly fewer instance
preemptions.

After the simulated day, the best- and worst-placed online instances from
the run serve REAL batched requests through the JAX serving engine, and
the Fig. 2 factor converts measured decode throughput into scheduled
performance.

  PYTHONPATH=src python examples/colocated_serving.py
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import numpy as np

from repro.core.colocation import (ColocationConfig, compare_day_cycle,
                                   compare_two_level, default_policies)
from repro.configs import get_config
from repro.jax_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Request, ServeEngine, TIER_PERF


def main() -> None:
    enable_compile_cache()
    # ---- the simulated day: topology-aware vs topology-unaware A/B -------
    cfg = ColocationConfig(num_nodes=12, seed=0, horizon_hours=24.0)
    print(f"simulating a {cfg.horizon_hours:.0f}h day on {cfg.num_nodes} "
          f"nodes (Table 3 mix, policies: "
          f"{[p.workload.name for p in default_policies(cfg)]}) ...")
    ab = compare_day_cycle(cfg, engines=("imp", "godel"))
    for name, rep in ab["reports"].items():
        print(f"  {name:6} scheduled-perf {rep.scheduled_perf:7.1f} "
              f"GPU-h | hit rate {rep.hit_rate:.0%} over "
              f"{rep.preemptions} preemptions | requeue "
              f"{rep.requeue_replanned}/{rep.requeued} replanned | "
              f"offline goodput {rep.offline_goodput:.0f} GPU-h")
    print(f"  scheduled-performance uplift: {ab['uplift'] * 100:+.1f}% "
          f"(preemptor slice {ab['preemptor_uplift'] * 100:+.1f}%; the "
          f"paper reports +55%)")

    # ---- the two-level backfill ladder on the same seeded day -------------
    bench = Path(__file__).parent.parent / "BENCH_elastic.json"
    if bench.exists():
        b = json.loads(bench.read_text())
        io_b, tl_b = b["modes"]["instance_only"], b["modes"]["two_level"]
        print(f"\ncommitted two-level A/B ({b['num_nodes']} nodes, "
              f"BENCH_elastic.json): offline goodput "
              f"{b['goodput_uplift'] * 100:+.1f}%, SLO attainment "
              f"{tl_b['slo_attainment']:.3f} vs {io_b['slo_attainment']:.3f}, "
              f"preemptions {io_b['preemptions']} -> {tl_b['preemptions']}")
    print("two-level request+instance ladder on this day:")
    two = compare_two_level(cfg)
    for name, rep in two["reports"].items():
        extra = (f" | request-level adm {rep.elastic_admitted} "
                 f"demote {rep.elastic_demoted} "
                 f"done {rep.elastic_completed}"
                 if name == "two_level" else "")
        print(f"  {name:13} offline goodput {rep.offline_goodput:7.1f} "
              f"GPU-h | SLO attainment {rep.slo_attainment:.3f} | "
              f"{rep.preemptions} preemptions, {rep.requeued} victims"
              f"{extra}")
    print(f"  ramps absorbed at request granularity: "
          f"{two['preemption_delta']:+d} preemptions, every victim requeue "
          f"avoided (goodput {two['goodput_uplift'] * 100:+.1f}% on this "
          f"small unsaturated day; the committed saturated protocol above "
          f"is the gated number)")

    # ---- serve real tokens at the day's achieved placement tiers ----------
    aware = ab["reports"]["imp"]
    ramp = max(aware.hours, key=lambda r: r.preemptions)
    print(f"\nbusiest ramp hour {ramp.hour:.0f}: {ramp.preemptions} "
          f"preemptions, {ramp.requeued} victims requeued, "
          f"mean decision factor {ramp.decision_factor_mean:.2f}")

    cfg_m = get_config("llama3.2-1b", smoke=True)
    api = build_model(cfg_m)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def batch():
        return [Request(rid=i,
                        prompt=rng.integers(1, cfg_m.vocab, 12,
                                            dtype=np.int32),
                        max_new_tokens=8) for i in range(4)]

    engine = ServeEngine(api, params, batch_size=2, seq_len=32)
    engine.run(batch())                     # jit warm-up, excluded
    t0 = time.perf_counter()
    engine.run(batch())
    dt = time.perf_counter() - t0
    raw_tps = engine.stats["tokens"] / 2 / dt   # stats span both runs
    print("decode throughput x Fig. 2 factor per placement tier:")
    for tier in sorted(TIER_PERF):
        factor = TIER_PERF[tier]
        print(f"  tier {tier}: {raw_tps:6.1f} tok/s raw x {factor:.2f} = "
              f"{raw_tps * factor:6.1f} tok/s scheduled")


if __name__ == "__main__":
    main()
