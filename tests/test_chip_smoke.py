"""chip_smoke.py's phase functions at a tiny config on the forced-CPU
mesh (XLA reference paths), and its refusal to run without a TPU.  The
real sizes only ever run on the chip; this keeps the script's control
flow, asserts and report keys from rotting between chip runs."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu.models.llama import llama_tiny_config  # noqa: E402

SERVE_KW = dict(prompt_lens=(3, 9, 21, 5, 14, 8), max_new_tokens=4,
                chunk=8, num_blocks=64, max_batch_size=3, block_size=4,
                expect_kernels=False)
TRAIN_KW = dict(batch=4, seq=32, steps=8, expect_kernels=False)


def test_main_refuses_cpu_backend_before_building(monkeypatch, capsys):
    def boom(*a, **k):
        raise AssertionError("built a phase without a TPU")
    for name in ("serve_phase", "train_phase", "flash_check",
                 "barrier_fact", "llama2_7b_width"):
        monkeypatch.setattr(chip_smoke, name, boom)
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ""                 # no result line on stdout
    assert "needs a TPU" in out.err


def test_serve_phase_tiny_cpu():
    r = chip_smoke.serve_phase(llama_tiny_config(), **SERVE_KW)
    assert r["requests"] == 6 and r["new_tokens"] == 24
    assert r["compiles"] <= len(r["token_budgets"])
    # f32 on the XLA reference path: the engine's tokens ARE the eager
    # argmax (on the chip, in bf16, this is reported and not gated)
    assert r["eager_argmax_agreement"] == 1.0
    assert set(r["ragged_check"]) == {"decode_only", "with_chunk", "tol"}


def test_ragged_check_reference_head_slices():
    """16 kv heads x 2 groups: the reference runs in 8 kv-head slices
    (the tiny model's 2 kv heads make it one slice, which hid a wrong
    slice offset until the first chip run)."""
    r = chip_smoke.ragged_check(32, 16, 16, 4, 6, 3, 8, (4, 8, 16),
                                expect_kernels=False)
    assert r["with_chunk"]["tokens"] == 10


def test_latent_check_slices_its_reference():
    """The chunk's reference goes in slices of 32 rows, each a span of
    its own over the same pages: a wrong slice offset shows as an error
    of order 1 against the (here XLA) launch over the whole pack."""
    r = chip_smoke.latent_check(heads=4, kv_lora=16, rope=8, block_size=4,
                                prefix=37, chunk=70, decodes=3,
                                expect_kernels=False)
    assert r["tokens"] == 73 and r["row"] == 128
    assert r["rel_err"] < 1e-2          # bf16 inputs, float32 both sides


def test_grouped_check_counts_held_valid_rows():
    """The plain loop weights an expert's output by the assignments
    that are held here AND tokens: 5 rows of padding and the rows held
    elsewhere add nothing, and one held expert gets no row."""
    r = chip_smoke.grouped_check(
        cases={"full": (24, 2, 4, 4, 0, 16, 24),
               "share": (24, 3, 16, 4, 4, 16, 24)}, expect_kernels=False)
    assert r["full"]["rows"] == (24 - 5) * 2
    assert 0 < r["share"]["rows"] < (24 - 5) * 3
    assert max(r["full"]["rel_err"], r["share"]["rel_err"]) < 1e-2


def test_train_phase_and_flash_check_tiny_cpu():
    r = chip_smoke.train_phase(llama_tiny_config(), **TRAIN_KW)
    assert r["compile_count"] == 1 and r["last_loss"] < r["first_loss"]
    f = chip_smoke.flash_check(1, 2, 64, 16, expect_kernels=False)
    assert max(f["rel_err"].values()) <= chip_smoke.FLASH_FWD_TOL


@pytest.mark.slow
def test_sharded_phases_tiny_cpu_mesh():
    """The four-device repeat: tp=4 serving and fsdp=2 x tp=2 training
    spread weights, pools and moments over four distinct devices."""
    from paddle_tpu.jit.spmd import mesh_2d, tp_mesh
    cfg = llama_tiny_config(num_key_value_heads=4)
    r = chip_smoke.serve_phase(cfg, mesh=tp_mesh(4), **SERVE_KW)
    assert r["eager_argmax_agreement"] == 1.0
    chip_smoke.train_phase(cfg, mesh=mesh_2d(2, 2), **TRAIN_KW)
