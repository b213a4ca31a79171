"""The readers of ``harness/program_gaps.py`` on a synthetic run: step
records written into a span log of their own, the benchmark's steps and
a trace reduction beside them."""
import pytest

from harness import program_gaps
from harness.result import Run
from harness.serve import StepRec, WindowResult

ENGINE = 5
STEP = 0.010                 # a step every 10 ms
W0, W1 = 1.0, 2.0            # the measured window
TRACE = {"t0": 0.5, "t1": 0.8}


def _record(step, t0, budget=8, jitter=0.0, built=0.0):
    """A launched step's record: the launch call 1.5 ms into the step,
    enqueued at 2 ms, tokens on the host at 8 ms (later by ``jitter``)."""
    return {"engine": ENGINE, "step": step, "budget": budget, "n_dec": 1,
            "n_pre": 0, "tokens": 1, "t0": t0, "t_admit": t0 + 0.0002,
            "t_pack": t0 + 0.0005, "t_fill": t0 + 0.001,
            "t_launch": t0 + 0.0015, "t_dispatch": t0 + 0.002,
            "t_tokens": t0 + 0.008 + jitter, "t_end": t0 + 0.0085 + jitter,
            "compiled": built > 0, "trace_s": built / 2,
            "lower_s": built / 4, "compile_s": built / 4}


def _steps(jitters, idle_at=None, start=0, builds=()):
    """Records of consecutive steps from ``start``; one of them, at
    ``idle_at``, launched nothing; ``builds`` the seconds of the first
    steps' builds."""
    out = []
    for i, jitter in enumerate(jitters):
        t0 = i * STEP
        r = _record(start + i, t0, jitter=jitter,
                    built=builds[i] if i < len(builds) else 0.0)
        if i == idle_at:
            r.update(budget=0, t_launch=r["t_end"], t_dispatch=r["t_end"],
                     t_tokens=r["t_end"], compiled=False)
        out.append(r)
    return out


def _shift(records, dt, first_step):
    return [dict(r, step=first_step + i,
                 **{k: v + dt for k, v in r.items()
                    if k == "t0" or k.startswith("t_")})
            for i, r in enumerate(records)]


@pytest.fixture
def log(monkeypatch):
    """A span log of its own, where the program's readers look."""
    import paddle_tpu.observability as obs
    from paddle_tpu.inference.serving import STEP_SPAN
    fresh = obs.SpanLog()
    monkeypatch.setattr(obs, "span_log", fresh)

    def write(records, drop=()):
        for r in records:
            fields = {k: v for k, v in r.items()
                      if k not in ("t0", "t_end") and k not in drop}
            fresh.record(STEP_SPAN, r["t0"], r["t_end"], cat="serving",
                         **fields)
    return write


def _run(window_records, busy_s=0.27):
    steps = [StepRec(r["t0"] - 1e-4, r["t_end"] + 1e-4, 1, 0, [], 1)
             for r in window_records]
    win = WindowResult(0.0, W0, W1, [], steps, 0, True, trace=dict(TRACE))
    return Run(None, None, win, 30.0,
               trace={"window_s": TRACE["t1"] - TRACE["t0"],
                      "busy_s": busy_s})


def _cell(log, drop=()):
    """Three build steps from 0, 20 steps in the traced part, 30 in the
    window; the window's step 10 launched nothing."""
    build = _steps([0.0] * 3, builds=(1.5, 0.75, 0.25))
    traced = _shift(_steps([0.0, 0.001, 0.0005] * 6 + [0.0, 0.0]), 0.6, 3)
    window = _shift(_steps([0.0, 0.0, 0.002] * 10, idle_at=10), 1.1, 23)
    log(build + traced + window, drop)
    return traced, window


def test_host_gap_is_the_median_over_launched_pairs(log):
    _, window = _cell(log)
    run = _run(window)
    gaps = program_gaps.host_gaps(window)
    # 29 consecutive pairs, the two that touch the idle step skipped
    assert len(gaps) == 27
    # from tokens at 8 ms (+ 2 ms every third step) to the next launch
    # enqueued at 10 + 2 ms
    assert sorted(set(round(g * 1e3, 6) for g in gaps)) == [2.0, 4.0]
    assert program_gaps.host_gap_ms(run) == pytest.approx(4.0)
    idle = window[10]
    assert not [g for a, b in zip(window, window[1:])
                if idle in (a, b) for g in program_gaps.host_gaps([a, b])]


def test_runtime_gap_is_the_idle_no_host_gap_fills(log):
    traced, window = _cell(log)
    run = _run(window, busy_s=0.150)
    gaps = program_gaps.host_gaps(traced)
    assert len(gaps) == 19
    want = (0.3 - 0.150 - sum(gaps)) / 19 * 1e3
    assert program_gaps.runtime_gap_ms(run) == pytest.approx(want)
    # no trace, no reading
    run.trace = None
    assert program_gaps.runtime_gap_ms(run) is None


def test_step_build_counts_the_start_up(log):
    _, window = _cell(log)
    assert program_gaps.step_build_s(_run(window)) == pytest.approx(2.5)


def test_a_log_without_the_first_steps_raises(log):
    traced = _shift(_steps([0.0] * 4), 0.6, 3)
    window = _shift(_steps([0.0] * 5), 1.1, 7)
    log(traced + window)
    with pytest.raises(RuntimeError, match="first steps"):
        program_gaps.step_build_s(_run(window))


@pytest.mark.parametrize("field,readers", [
    ("t_launch", ("host_gap_ms", "runtime_gap_ms")),
    ("trace_s", ("step_build_s",))])
def test_a_program_without_the_fields_reads_none(log, field, readers):
    _, window = _cell(log, drop=(field,))
    run = _run(window)
    for name in readers:
        assert getattr(program_gaps, name)(run) is None
