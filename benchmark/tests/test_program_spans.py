"""The per-layer metrics that read the program's own step records
(``harness/program_spans.py``), on the tiny cells of ``tinytree`` on the
CPU: a traced run reports every one of its kind, the records pair one to
one with the benchmark's own steps and say what its reconstruction says,
a log emptied in mid-run makes the readers raise, and a program without
the record leaves the metrics out.  ``tools/scope_trace.py`` runs the same
tiny cell: the CPU's trace has the host spans alone.
"""
import importlib.util
import os
import time

import pytest

import run as bench_run
import tinytree
from harness import program, program_spans, serve

CHAT = {"engine_host_ms.chat", "budget_fill.chat", "chunk_step_share.chat",
        "decode_launch_ms.chat", "chunk_launch_ms.chat"}
DOCS = {"engine_host_ms.docs", "budget_fill.docs", "chunk_launch_ms.docs"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinytree.make(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def scope_trace():
    spec = importlib.util.spec_from_file_location(
        "bench_tool_scope_trace",
        os.path.join(tinytree.BENCH, "tools", "scope_trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traced(workload, seed, root, monkeypatch):
    """A traced run, and the ``Run`` its readers saw."""
    seen = {}
    read = bench_run.read_metrics

    def spy(run, traced):
        seen["run"] = run
        return read(run, traced)

    monkeypatch.setattr(bench_run, "read_metrics", spy)
    result, rc = bench_run.run_cell(workload, seed, 2.0, True, root=root,
                                    require_chip=False)
    assert rc == 0 and result["correct"] is True
    return result, seen["run"]


def test_chat_cell_reports_the_step_record_metrics(root, monkeypatch):
    result, run = _traced("tiny-mistral.chat", 2 ** 31 + 11, root,
                          monkeypatch)
    got = result["metrics"]
    assert CHAT <= set(got) and not DOCS & set(got)
    assert all(got[name]["value"] > 0 for name in CHAT)
    assert got["budget_fill.chat"]["value"] <= 100
    assert got["chunk_step_share.chat"]["value"] < 100
    # the step is its launch and the engine's host time: the benchmark's
    # own clock round engine.step() holds both, and little more
    steps = run.steps_in()
    records = program_spans.step_records(run)
    assert len(records) == len(steps) > 20
    for rec, step in zip(records, steps):
        assert step.t0 <= rec["t0"] <= rec["t_end"] <= step.t1
        assert (step.t1 - step.t0) - (rec["t_end"] - rec["t0"]) < 5e-3
        # no prefix sharing here: the reconstruction from outside and
        # the record agree on what the step carried
        assert rec["n_dec"] == step.decode_tokens
        assert rec["n_pre"] == step.prefill_tokens
        assert sorted((q, kv) for _, q, kv in rec["spans"].tolist()) \
            == sorted(step.spans)
    # ... and the program without the record leaves the metrics out
    from paddle_tpu.inference import serving
    monkeypatch.delattr(serving, "STEP_SPAN")
    assert program_spans.step_records(run) is None
    assert program_spans.engine_host_ms(run) is None
    assert program_spans.step_launch_ms(run, chunk=True) is None


def test_docs_cell_reports_the_step_record_metrics(root, monkeypatch):
    result, run = _traced("tiny-mixtral.docs", 12, root, monkeypatch)
    got = result["metrics"]
    assert DOCS <= set(got) and not CHAT & set(got)
    assert all(got[name]["value"] > 0 for name in DOCS)
    launched = program_spans.launched(run)
    assert sum(r["tokens"] for r in launched) \
        == sum(s.prefill_tokens + s.decode_tokens for s in run.steps_in())


def test_a_log_emptied_in_mid_run_raises(root, monkeypatch):
    from paddle_tpu.observability import span_log
    build, run_window = program.build_engine, serve.run_window
    clear_at = []

    def emptied(model, engine_kw):
        eng = build(model, engine_kw)
        step = eng.step

        def stepping():
            out = step()
            if clear_at and time.perf_counter() > clear_at[0]:
                span_log.clear()
                clear_at.clear()          # once
            return out

        eng.step = stepping
        return eng

    def window(eng, plan, *rest):         # a second into the window
        clear_at.append(time.perf_counter() + plan.warmup_s + 1.0)
        return run_window(eng, plan, *rest)

    monkeypatch.setattr(program, "build_engine", emptied)
    monkeypatch.setattr(serve, "run_window", window)
    with pytest.raises(RuntimeError, match="step records for the window"):
        bench_run.run_cell("tiny-mistral.chat", 5, 2.0, True, root=root,
                           require_chip=False)
    assert not clear_at


def test_scope_trace_on_the_cpu(scope_trace, root, tmp_path):
    out = str(tmp_path / "trace")
    got = scope_trace.trace_cell("tiny-mistral.chat", 3, 1.0, out,
                                 root=root, require_chip=False)
    assert os.path.getsize(got["file"]) == got["bytes"] > 0
    assert got["file"].startswith(out)
    assert got["steps"]["decode"] > 0 and got["steps"]["chunk"] > 0
    assert set(got["steps"]["budgets"]) - {0} <= {4, 8, 16, 32}
    assert got["idle_gaps"]["over_1ms"] == 0 and got["op_seconds"] == 0


def test_scope_trace_reductions(scope_trace):
    ms = 1_000_000
    steps = [(0, 100 * ms, 7), (100 * ms, 300 * ms, 8)]
    phases = [(0, 10 * ms, "engine.admit"), (10 * ms, 20 * ms, "engine.pack"),
              (20 * ms, 30 * ms, "engine.dispatch"),
              (30 * ms, 95 * ms, "engine.fetch"),
              (95 * ms, 100 * ms, "engine.book"),
              (100 * ms, 112 * ms, "engine.admit")]
    ops = [("fusion.1", 31 * ms, 60 * ms), ("copy.2", 60 * ms, 94 * ms),
           ("fusion.1", 113 * ms, 290 * ms), ("other.3", 400 * ms, 401 * ms)]
    records = {7: {"budget": 64, "n_pre": 0}, 8: {"budget": 1024, "n_pre": 512}}
    scopes = {64: {"fusion.1": "ffn", "copy.2": None},
              1024: {"fusion.1": "moe.experts"}}
    table, detail = scope_trace.by_scope(ops, steps, records, scopes.get)
    assert table["decode"] == {"ffn": 0.029, scope_trace.NO_SCOPE: 0.034}
    assert table["chunk"] == {"moe.experts": 0.177}
    assert table["outside a step"] == {scope_trace.NO_SCOPE: 0.001}
    assert detail[("moe.experts", "fusion")] == 0.177
    idle = scope_trace.idle_gaps(ops, phases)
    # 94..113 ms: 1 of fetch, 5 of book, 12 of the next step's admission,
    # 1 after it; 290..400 ms lies outside every phase
    assert idle["over_1ms"] == 2 and idle["under_1ms"]["gaps"] == 0
    assert idle["owners"] == {"engine.admit": 1, "between steps": 1}
    assert idle["seconds_by_phase"] == pytest.approx(
        {"between steps": 0.111, "engine.admit": 0.012,
         "engine.book": 0.005, "engine.fetch": 0.001})
    # one gap lies after a fetch and before a dispatch; none follows it
    assert idle["clock_lead_ms"] == [None, None]
    assert idle["median_ms"]["gap"] == 110.0
    assert scope_trace.instruction(
        "%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") == "fusion.12"
