"""Hard proofs that the chip did the work, copied from `chip_smoke.py`. A
configuration lists the ones it needs under `proofs`; each returns a line
for the record or raises ProofFailed."""


class ProofFailed(Exception):
    pass


def ledger_platform_tpu(ctx) -> dict:
    from spark_rapids_tpu.obs import dispatch
    platforms = sorted({p["platform"] for p in dispatch.programs()})
    n = dispatch.counters()["dispatches"]
    if platforms != [ctx["platform"]] or n <= 0:
        raise ProofFailed(f"ledger platforms {platforms}, {n} dispatches; "
                          f"expected [{ctx['platform']!r}]")
    return {"platforms": platforms, "dispatches": n}


def no_open_breaker(ctx) -> dict:
    from spark_rapids_tpu.exec import lifecycle
    lc = lifecycle.counters()
    if lifecycle.open_breakers() or \
            lc["breaker_open"] != ctx["lifecycle0"]["breaker_open"]:
        raise ProofFailed(f"breaker opened: {lifecycle.open_breakers()}, {lc}")
    return {"open_breakers": [], "breaker_trips": 0}


def no_task_retry(ctx) -> dict:
    from spark_rapids_tpu.exec import lifecycle
    lc = lifecycle.counters()
    if lc["whole_plan_retries"] != ctx["lifecycle0"]["whole_plan_retries"]:
        raise ProofFailed(f"a task was retried: {lc}")
    return {"task_retries": 0}


PROOFS = {f.__name__: f for f in (ledger_platform_tpu, no_open_breaker,
                                  no_task_retry)}


def run_proofs(names: list, ctx: dict) -> dict:
    return {n: PROOFS[n](ctx) for n in names}
