"""`beta_rewrite_ms` reads host ms per scheduling step inside the
program's ``engine.set_betas`` span: the spans' sum over the number of
``sched.step`` spans, and nothing without steps."""

from __future__ import annotations

import pytest

from harness import spec, tracing

SAMPLE = spec.BENCH_DIR / "trace_sample" / "aqua-multi.anneal-backlog.json.gz"


def test_reads_the_set_betas_time_per_step():
    read = spec.reader("beta_rewrite_ms.anneal")
    steps = [0.01] * 4
    assert read({"trace": {"spans": {"sched.step": steps,
                                     "engine.set_betas": [0.001, 0.003]}}}) == pytest.approx(1.0)
    assert read({"trace": {"spans": {"sched.step": steps}}}) == 0.0
    assert read({"trace": {"spans": {"engine.set_betas": [0.004]}}}) is None
    assert read({"trace": None}) is None and read({}) is None


def test_reads_the_per_job_writes_of_the_recorded_backlog_window():
    """The committed chip sample was recorded when every due anneal job
    wrote its own beta: hundreds of ms of them a step."""
    r = tracing.reduce(tracing.load(str(SAMPLE)))
    got = spec.reader("beta_rewrite_ms.anneal")({"trace": r})
    want = 1e3 * sum(r["spans"]["engine.set_betas"]) / len(r["spans"]["sched.step"])
    assert got == pytest.approx(want) and got > 100
