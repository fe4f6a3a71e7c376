"""Package-query admission: each tick admits the batch of greatest
priority within the request-count, KV-cache and FLOP budgets."""
import numpy as np
import pytest

from repro.serving import PackageScheduler


def test_scheduler_respects_budgets_and_beats_fcfs(lm_request):
    rng = np.random.default_rng(0)
    reqs = [lm_request(i, int(rng.integers(16, 512)),
                       int(rng.integers(16, 256)),
                       float(rng.uniform(0.01, 1.0))) for i in range(200)]
    hbm = 2e9
    flops = 1e14
    sched = PackageScheduler(hbm_budget_bytes=hbm, flop_budget=flops,
                             max_batch=32)
    for r in reqs:
        sched.submit(r)
    batch = sched.tick()
    assert 0 < len(batch) <= 32
    assert sum(r.kv_bytes for r in batch) <= hbm * (1 + 1e-6)
    assert sum(r.prefill_flops for r in batch) <= flops * (1 + 1e-6)
    # FCFS baseline under the same budgets
    fcfs, kv, fl = [], 0.0, 0.0
    for r in reqs:
        if len(fcfs) < 32 and kv + r.kv_bytes <= hbm \
                and fl + r.prefill_flops <= flops:
            fcfs.append(r)
            kv += r.kv_bytes
            fl += r.prefill_flops
    assert sum(r.priority for r in batch) >= sum(r.priority for r in fcfs)


@pytest.mark.parametrize("cost", ["kv_bytes", "prefill_flops"])
def test_request_over_a_budget_alone_stays_queued(cost, lm_request):
    rng = np.random.default_rng(1)
    reqs = [lm_request(i, int(rng.integers(16, 512)),
                       int(rng.integers(16, 256)),
                       float(rng.uniform(0.01, 1.0))) for i in range(20)]
    sched = PackageScheduler(hbm_budget_bytes=2e9, flop_budget=1e14,
                             max_batch=8)
    budget = {"kv_bytes": 2e9, "prefill_flops": 1e14}[cost]
    big = lm_request(99, 64, 64, 10.0)   # the highest priority by far
    setattr(big, cost, 1.5 * budget)
    for r in reqs[:10] + [big] + reqs[10:]:
        sched.submit(r)
    admitted = []
    for _ in range(10):
        batch = sched.tick()
        assert sched.last_report.status == "ok"
        admitted.extend(r.rid for r in batch)
        if not batch:
            break
    assert sorted(admitted) == list(range(20))
    assert sched.queue == [big]
    assert len(sched._store) == 1
