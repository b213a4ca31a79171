"""Both kinds of cell end to end at a tiny size on the CPU, in a temp copy
of the benchmark's data to which a throw-away cell of each kind, a
throw-away metric and a throw-away family whose layers are of two kinds
were ADDED (no existing file edited): what a later PR does.  The measurement path itself refuses the CPU; these tests skip that
look for a chip and drive the rest of a run.
"""
import hashlib
import json
import os
import subprocess
import sys

import pytest

import run as bench_run
import tinytree


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinytree.make(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def chat(root):
    return bench_run.run_cell("tiny-mistral.chat", 2 ** 31 + 7, 2.0, False,
                              root=root, require_chip=False)


def test_cli_refuses_the_cpu():
    """No TPU: exit 2 and not one line on standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tinytree.BENCH, "run.py"),
         "--workload", "mistral-7b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tinytree.ROOT, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_open_loop_cell_reports_its_end_to_end_metrics(chat):
    result, rc = chat
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert result["attempted"] >= 8 and result["failed"] == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "compared"


def test_reference_agrees_with_the_program_in_float32(chat):
    """Mistral reference vs the system at a tiny size: in float32 the
    served token is the reference's best at every sampled position."""
    result, _ = chat
    assert result["reference"]["tokens_off_reference_best"] == 0
    assert result["compared"]["logit_gap_max"][0] <= 1e-3
    assert result["reference"]["positions"] >= 12


def test_closed_loop_moe_cell_traced_with_added_metric(root):
    """Mixtral reference vs the system; the traced run reports the
    per-layer metrics that need no device, and the metric this tree added
    (``steps_total``, no ``workloads`` key, moves ``setup_s``)."""
    result, rc = bench_run.run_cell("tiny-mixtral.docs", 11, 2.0, True,
                                    root=root, require_chip=False)
    assert rc == 0 and result["correct"] is True
    assert result["reference"]["tokens_off_reference_best"] == 0
    assert "batch_occupancy.docs" in result["metrics"]
    assert result["metrics"]["steps_total"]["value"] > 0
    # no device plane on the CPU: the trace readers return nothing, and
    # the harness leaves them out rather than printing 0
    assert "ragged_attn_roofline.docs" not in result["metrics"]
    assert "device_idle.docs" not in result["metrics"]


def test_family_of_two_kinds_of_layer_comes_as_files_only(root):
    """``densefirst`` (layer 0 dense, layers 1-2 sparse: its reference, its
    configuration and its cell are files of the throw-away tree) through
    the real entry point: the program serves what its reference puts
    first, and the router's margins come from the sparse layers alone."""
    result, rc = bench_run.run_cell("tiny-densefirst.docs", 12, 2.0, False,
                                    root=root, require_chip=False)
    assert rc == 0 and result["correct"] is True
    assert result["reference"]["tokens_off_reference_best"] == 0
    assert result["reference"]["positions"] >= 12
    assert result["compared"]["undecided_share"][0] < 0.2
    assert not os.path.exists(
        os.path.join(tinytree.BENCH, "references", "densefirst.py"))


def test_kinds_that_disagree_with_the_layers_fail_at_the_leaves(root):
    """The reference says layer 0 is dense; a configuration that maps the
    kinds to the other classes builds a sparse layer there."""
    from harness import program, spec
    cell = spec.Cell("tiny-densefirst.docs", root)
    prog = cell.config["program"]
    cls = prog["layer_class"]
    cell.config = dict(cell.config, program=dict(prog, layer_class={
        "dense": cls["sparse"], "sparse": cls["dense"]}))
    with pytest.raises(RuntimeError, match=r"layer 0 \(kind 'dense'\).*"
                       r"MixtralDecoderLayer"):
        program.build_model(cell, 0)


# sha256 (16 hex digits) of every group's leaves, in the order of the
# reference's shapes, as float32 bytes: taken on PR 25's tree (one
# ``layer_class``, one ``layer_shapes``) at tinytree's sizes, seed
# 2**31 + 7, groups top, layer 0, layer 1
PARENTS_GROUPS = {
    ("mistral", "float32"): ["23c097ad2bab10cb", "ec24dadad0f1e2bc",
                             "f10ee93e6cbc6703"],
    ("mistral", "bfloat16"): ["b101362a087826cf", "1c4d16fd8142fba8",
                              "bb6066634d509367"],
    ("mixtral", "float32"): ["23c097ad2bab10cb", "f2aff05d6d42a7a3",
                             "3a4699a8967ad4c7"],
    ("mixtral", "bfloat16"): ["b101362a087826cf", "83937e1bfa725a5d",
                              "98027114ade32000"],
}


@pytest.mark.parametrize("family,dtype", sorted(PARENTS_GROUPS))
def test_seeded_weights_are_the_parents(root, family, dtype):
    """A family of one kind gets the group index, the shapes and so the
    values it got before layers could be of more than one kind."""
    import numpy as np
    from harness import spec, weights
    cell = spec.Cell(f"tiny-{family}."
                     + ("chat" if family == "mistral" else "docs"), root)
    cfg, ref = dict(cell.config, dtype=dtype), cell.reference()
    kinds, shapes = spec.family_layers(ref, cfg)
    assert kinds == [None, None]
    groups = [(weights.TOP, ref.top_shapes(cfg))] + [
        (li, shapes[kind]) for li, kind in enumerate(kinds)]
    sums = []
    for group, leaves in groups:
        vals = weights.make_group(2 ** 31 + 7, group, leaves, dtype)
        h = hashlib.sha256()
        for name in leaves:
            h.update(name.encode())
            h.update(np.asarray(vals[name].astype("float32")).tobytes())
        sums.append(h.hexdigest()[:16])
    assert sums == PARENTS_GROUPS[family, dtype]


def test_same_seed_same_requests(root):
    from harness import spec, traffic
    cell = spec.Cell("tiny-mistral.chat", root)
    a = traffic.make_plan(cell.traffic, cell.deploy, 512, 5, 2.0)
    b = traffic.make_plan(cell.traffic, cell.deploy, 512, 5, 2.0)
    assert [(r.due, r.n_out, r.prompt.tolist()) for r in a.requests] == \
        [(r.due, r.n_out, r.prompt.tolist()) for r in b.requests]


@pytest.mark.parametrize("cell, number", [
    ("tiny-mistral.chat", "logit_gap_max"),
    ("tiny-mixtral.docs", "wide_gap_share")])
def test_altered_token_is_not_correct(root, monkeypatch, cell, number):
    """The timed path broken underneath: every seventh launch's tokens are
    altered where they are produced.  ``correct`` must come out false, by
    the widest gap where that is compared and by the share of wide gaps
    in the sparse cell."""
    from harness import program
    build_engine = program.build_engine

    def build_tampered(model, engine_kw):
        eng = build_engine(model, engine_kw)
        orig = eng.mixed.call_packed
        calls = [0]

        def call_packed(pack, T, q_probs=None):
            out = orig(pack, T)
            calls[0] += 1
            if calls[0] % 7 == 0:
                out = (out + 1) % eng.cfg.vocab_size
            return out
        eng.mixed.call_packed = call_packed
        return eng

    monkeypatch.setattr(program, "build_engine", build_tampered)
    result, rc = bench_run.run_cell(cell, 3, 2.0, False, root=root,
                                    require_chip=False)
    assert rc == 0 and result["correct"] is False
    value, limit = result["compared"][number]
    assert value > limit


@pytest.mark.parametrize("cell", ["tiny-mistral.chat", "tiny-mixtral.docs",
                                  "tiny-densefirst.docs"])
def test_int8_control_is_not_correct(root, monkeypatch, cell):
    """The control (the reference computed in int8, in the program's place,
    on the same prompts and served tokens) goes through the run's own
    comparison and fails the cell's limits; the program, in the same run,
    reads under them."""
    from harness import correct
    monkeypatch.setattr(correct, "IN_PROGRAMS_PLACE", "int8")
    result, rc = bench_run.run_cell(cell, 4, 2.0, False, root=root,
                                    require_chip=False)
    assert rc == 0 and result["correct"] is False
    compared, ref = result["compared"], result["reference"]
    assert ref["in_programs_place"] == "int8"
    names = [n for n in compared if n in ref["program"]]
    assert len(names) == 2
    assert any(compared[n][0] > compared[n][1] for n in names)
    assert all(ref["program"][n] <= compared[n][1] for n in names)
    assert compared["unfinished_after_drain"] == [0, 0]


def test_bf16_witness_reports_its_routing(root, monkeypatch):
    """The witness: the reference with bfloat16 activations, held against
    the float32 reference's routing."""
    from harness import correct
    monkeypatch.setattr(correct, "IN_PROGRAMS_PLACE", "bf16")
    result, _ = bench_run.run_cell("tiny-mixtral.docs", 4, 2.0, False,
                                   root=root, require_chip=False)
    ref = result["reference"]
    assert ref["control_flipped_positions"] >= ref["control_flipped_and_decided"]
    assert result["compared"]["undecided_share"][0] < 0.2
    assert "0.1" in ref["by_margin_min"]


def test_adding_a_cell_edited_no_existing_file(root):
    """Every data file of the real tree is byte-identical in the copy that
    runs the added cells."""
    for sub in tinytree.DATA:
        for name in os.listdir(os.path.join(tinytree.BENCH, sub)):
            if name.startswith("__"):
                continue
            with open(os.path.join(tinytree.BENCH, sub, name), "rb") as f:
                a = f.read()
            with open(os.path.join(root, "benchmark", sub, name), "rb") as f:
                assert f.read() == a, name
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        added = json.load(f)
    with open(os.path.join(tinytree.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    assert added["workloads"][:len(real["workloads"])] == real["workloads"]
